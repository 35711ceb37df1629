"""Host clock from the start of the process to the start of the window:
imports, native planes, compile-cache loads or compiles, the warm-up job."""


def read(run):
    return run.setup_s
