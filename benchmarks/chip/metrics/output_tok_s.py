"""Output tokens that reached the host inside the window, over the
window's seconds.  Tokens of requests still running at the end count."""
from harness import reading


def read(rec):
    return reading.window_tokens(rec) / rec.window_s
