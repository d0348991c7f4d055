"""peak_hbm_gb: the most device memory in use on any of the cell's chips
over the run (``peak_bytes_in_use``), in GB of 1e9 bytes."""


def read(x):
    return x.peak_bytes / 1e9
