"""Programs that went through the backend compile step during set-up
(compiled by XLA or read back from the persistent cache)."""


def read(run):
    return run.setup_compiles
