"""Tokens of the steps completed inside the window (ended by waiting for the
last step's loss), over the window and the chips."""


def read(run):
    if not run.steps:
        return None
    return len(run.steps) * run.tokens_per_step / run.window_s / run.chips
