"""host_ms_per_batch.prefill: mean over the window's prefill batches of the
wall time from the batch's virtual start to its completion, minus the ms
the program's JaxExecutor measured around its own device call: padding,
device_put, the pacing adapter and the loop's own work before the call."""


def read(run):
    b = run.batches
    return sum(end - start - inner for _, start, end, inner, *_ in b) / len(b) if b else None
