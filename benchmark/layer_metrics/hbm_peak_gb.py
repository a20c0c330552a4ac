"""What one chip has to hold while training: the peak of live buffers
(memory_stats()["peak_bytes_in_use"], largest over the cell's devices, read
after warm-up and before the reference check) plus the compiled train step's
temporaries (its memory_analysis()), which the TPU runtime's counter leaves
out.  The same number as device.memory_peak_bytes."""


def read(run):
    if not run["train_buffers_peak_bytes"]:  # a backend without memory_stats()
        return None
    return (run["train_buffers_peak_bytes"] + run["step_temporary_bytes"]) / 1e9
