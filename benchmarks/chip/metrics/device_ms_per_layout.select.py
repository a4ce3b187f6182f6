"""Device-busy milliseconds per layout scored in the traced window."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("driver") != "select" or not rec["layouts"]:
        return None
    return 1e3 * tr["busy_s"] / rec["layouts"]
