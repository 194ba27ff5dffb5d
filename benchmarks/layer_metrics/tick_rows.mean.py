"""Engine tick: decode rows in a tick, mean over the window's ticks."""


def read(run):
    xs = [t.get("decode_rows", 0) for t in run.window_ticks()]
    return sum(xs) / len(xs) if xs else None
