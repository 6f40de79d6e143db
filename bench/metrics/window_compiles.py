"""Programs obtained inside the measured window: backend compiles plus
persistent-cache loads, counted through JAX's monitoring events."""


def read(run):
    return float(run.window.programs_in_window)
