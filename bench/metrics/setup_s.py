"""Set-up time: process start to the first timed unit (host clock)."""


def read(rec):
    return rec["setup_s"]
