"""Output tokens delivered to clients inside the window, per second of
window (host clock, client side)."""


def value(rec):
    c = rec["client"]
    return c["tokens_in_window"] / c["seconds"]
