"""setup_s: process start to the measured window's start (import, chip
start-up, weights, engine, plan, every compile or cache load, the
warm-up requests), host clock."""


def read(run):
    return run.setup_s
