"""The chip's peaks, and the bytes a query needs.

One table, keyed by ``device_kind`` as JAX reports it; a device that is not
in it is an error, never a default.
"""

import numpy as np

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s per chip.  JAX names the chip "TPU v5 lite".
_V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return PEAKS[device_kind]


def bytes_needed(column_dtypes, args, rows):
    """What a groupby has to read, whatever implements it: every row of its
    files once, in each distinct key, measure and filter column, at the
    width the configuration stores the column in.  A groupby is bound by
    bytes: its arithmetic is one add per row and measure."""
    _files, gcols, aggs, where = args
    columns = set(gcols) | {a[0] for a in aggs} | {w[0] for w in where}
    return int(rows) * sum(np.dtype(column_dtypes[c]).itemsize for c in columns)
