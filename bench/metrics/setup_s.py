"""setup_s: process start to the start of the measured window: JAX start,
DB generation, the miner's build and one warm-up fit, which compiles or
loads every program the job uses (host clock)."""


def read(x):
    return x.setup_s
