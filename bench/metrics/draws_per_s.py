"""Kept draws of every chain of every job completed in the window, over
the window's wall time (first call's start to last call's end). A job's
warm-up transitions are inside that time but produce no kept draw."""


def read(rec):
    if rec["driver"]["kind"] != "chains":
        return None
    return rec["work"]["draws"] / rec["window_s"]
