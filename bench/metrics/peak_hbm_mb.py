"""peak_bytes_in_use of the fullest chip after the window, in MB
(1e6 bytes): what the process held on the device at its peak."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 1e6
