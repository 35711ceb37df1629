"""XLA compiles the recompile sentinel counted inside the window (a
program read back from the persistent cache counts: it was not warm)."""


def read(run):
    return run.compiles
