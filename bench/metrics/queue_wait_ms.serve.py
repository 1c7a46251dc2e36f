"""Mean wait in the scheduler's queue over the window: the delta of the
repro.serve.queue_wait_s histogram's sum over the delta of its count (the
program's counter; its bucket quantiles are interpolated, its mean is
not)."""


def read(rec):
    if not rec.get("queue_wait_count"):
        return None
    return 1e3 * rec["queue_wait_sum_s"] / rec["queue_wait_count"]
