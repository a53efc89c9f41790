"""Output tokens of the requests that completed inside the window over the
window (client side: nothing streams, so a caller sees tokens only at
completion). With some thirty long requests a window, one request ending just
before or after the window's end moves this by a tenth: no bound."""


def read(run):
    return run["end_to_end"].get("decode_tok_per_s")
