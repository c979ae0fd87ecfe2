"""Process start to the first timed round: weights made on the device from
the seed, the loop built, every reachable kv_live program built and run."""


def read(run):
    return run.setup_s
