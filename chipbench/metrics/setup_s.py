"""Set-up: from process start to the first timed request, compilation,
fit or load, traffic generation and warm-up included (host clock)."""


def read(run):
    return run.setup_s
