"""Process start to the start of the window (host clock): backend start-up,
data generation, run_training's own set-up, every compilation, epoch 0."""


def compute(run):
    return run.setup_s
