"""Process start to the first scheduled arrival: weights, engine, compile
(or the compile cache) and the engine's shape warm-up."""


def read(run):
    return run.setup_s
