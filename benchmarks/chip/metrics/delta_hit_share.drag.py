"""Percent of the window's frames that the program served incrementally
(its ``flags["incremental"]`` on the returned scores)."""


def read(rec):
    if rec.get("driver") != "drag" or not rec["frames"]:
        return None
    return 100.0 * rec["delta_hits"] / rec["frames"]
