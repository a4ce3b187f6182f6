"""Layouts scored per second over the window's whole calls (host clock)."""


def read(rec):
    return rec.get("layouts_per_s")
