"""Events handed over in the window over the whole window's seconds
(``t0`` -> ``env.execute()`` returned: drain, last fires and last sink
writes inside it)."""


def read(run):
    return run.events / run.window_s if run.events else None
