"""Process start to the first measured request or step: imports, weights,
compilation or cache reads, warm-up, the correctness checks, warm traffic."""


def read(run):
    return run.t0 - run.t_process_start
