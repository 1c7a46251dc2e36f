"""Images completed per second: batch x passes over the window (host
clock, every pass ends in block_until_ready)."""


def read(rec):
    if "images" not in rec:
        return None
    return rec["images"] / rec["window_s"]
