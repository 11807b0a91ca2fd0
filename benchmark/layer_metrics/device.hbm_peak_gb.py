"""Peak device memory of the fullest chip, in GB (1e9 bytes): the runtime's
``peak_bytes_in_use + peak_bytes_reserved`` (the reserved part is where an
executable's temporaries live), or what the runner's executable declares
(arguments + outputs - aliased + temporaries) where that is larger; the
run's ``memory_source`` line says which.  The runtime's sum is of two peaks
that need not fall together, so it is an upper estimate (R-FCN: 7.82 GB
against 7.09 GB declared).  Source: program counter."""


def read(run):
    return run.hbm_bytes / 1e9 if run.hbm_bytes else None
