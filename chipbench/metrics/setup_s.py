"""Process start to the opening of the window: imports, parameters made on
the device, compiles or compile-cache loads, warm-up and the set-up's
admissions (host clock)."""


def value(run):
    return run.setup_s
