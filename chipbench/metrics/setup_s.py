"""Process start to the start of the window: imports, mesh, weights made
on the device, deployment, KV pool growth, warm-up and compiles."""


def read(run):
    return run.setup_s
