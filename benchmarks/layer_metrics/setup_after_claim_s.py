"""The part of ``setup_s`` that the repo controls: chip claimed -> start of
the window (importing the program, the session, the table from the seed on
the device, program loads and the warm-up jobs). What comes before it
(interpreter, ``import jax``, the TPU runtime's start-up) is the platform's:
9 to 17.5 s, drifting by up to 12 % between two sets of the same code."""


def read(run):
    return run["setup_s"] - run["setup_phases"]["chip_claimed"]
