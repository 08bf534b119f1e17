"""95th percentile, over every request due inside the window, of the
time from its scheduled arrival to the host holding its first token; a
request with no first token by the window's end counts at (end - due)."""
from harness import reading


def read(rec):
    v = reading.percentile(reading.ttft(rec), 95)
    return None if v is None else 1e3 * v
