"""Device-busy milliseconds inside one execution of the ``train_step``
executable, from the device trace."""


def read(run):
    trace = run.get("trace")
    return None if not trace else trace.get("module_device_ms")
