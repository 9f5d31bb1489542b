"""Peak device memory on the fullest chip after the window: the
runtime's ``peak_bytes_in_use`` (buffers) plus ``peak_bytes_reserved``
(scratch of the loaded programs), in GB (serving cells)."""


def read(run):
    return run.hbm_peak_gb()
