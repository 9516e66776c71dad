"""Tokens the engine emitted between the window's opening and closing
steps, over the seconds between them."""


def read(rec):
    c = rec["counters"]
    return c["tokens_decoded"] / c["t"]
