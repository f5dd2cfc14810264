"""Peak device memory in use over the run (the fullest chip), in GiB, as
the device's allocator reports it after the window."""


def read(run):
    b = run.memory_peak_bytes
    return None if b is None else b / 2 ** 30
