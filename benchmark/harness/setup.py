"""Where a run's set-up goes: wall-clock marks between its phases, printed as
the ``[setup]`` line. Not a metric; ``setup_s`` is their sum."""

import time

import jax


class SetupClock:
    """Started where the runner starts (what came before is ``imports``)."""

    def __init__(self):
        self.marks = [("imports", time.time())]

    def mark(self, phase, *wait_for):
        """The phase that ends now, once ``wait_for`` is ready on the device;
        returns the wall time."""
        jax.block_until_ready(wait_for)
        self.marks.append((phase, time.time()))
        return self.marks[-1][1]


def setup_line(t0, marks):
    """``[setup] imports 3.1 + weights 0.4 + ... = 24.5 s`` from the process's
    first instant ``t0`` and a runner's marks."""
    parts, last = [], t0
    for phase, at in marks:
        parts.append(f"{phase} {at - last:.2f}")
        last = at
    return "[setup] " + " + ".join(parts) + f" = {last - t0:.2f} s"
