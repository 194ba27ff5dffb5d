"""Output tokens delivered to clients inside the window, over the window."""


def read(run):
    n = sum(1 for r in run.requests for t in r.token_times if run.in_window(t))
    return n / run.window_s if n else None
