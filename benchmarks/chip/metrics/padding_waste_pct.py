"""padding_waste_pct: 1 - real tokens / (executed batch size x bucket),
summed over the window's prefill batches, in percent."""


def read(run):
    b = run.batches
    if not b:
        return None
    real = sum(sum(lengths) for *_, lengths in b)
    padded = sum(k * bucket for _, _, _, _, k, bucket, _ in b)
    return 100.0 * (1.0 - real / padded)
