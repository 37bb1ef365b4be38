"""Start of the process (the first line of ``run.py``) -> start of the
window: interpreter, ``import jax``, the claim of the chip, importing the
program, the session, the table from the seed on the device, the warm-up
jobs (compilation in a cold run)."""


def read(run):
    return run["setup_s"]
