"""Real request lanes over padded bucket lanes dispatched in the window
(deltas of repro.serve.occupied_lanes and repro.serve.bucket_lanes)."""


def read(rec):
    if not rec.get("bucket_lanes"):
        return None
    return 100.0 * rec["occupied_lanes"] / rec["bucket_lanes"]
