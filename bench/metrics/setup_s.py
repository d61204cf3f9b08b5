"""Set-up time: process start to the end of the warm-up (imports, JAX and
the device, the compilation cache or compiler, inputs, plans and the
warm-up requests), on the host clock."""


def read(run):
    return run.setup_s
