"""peak_device_gb: the fullest chip's ``peak_bytes_in_use`` after the
window, in 10**9 bytes: the largest graph a chip holds sets what users
can generate."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
