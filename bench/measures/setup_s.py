"""Set-up: from the start of the process (JAX's import and start-up
included) to the start of the window: the data, the system, and the
warm-up that compiles or loads every program the window runs."""


def measure(run):
    return run["setup_s"]
