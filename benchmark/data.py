"""Seeded taxi shards: the frames, and the shard files the worker serves.

A copy of ``chip_smoke.py``'s ``shard_frame`` / ``build_dataset`` (the
benchmark does not import the smoke: later PRs may change it), with the
uniform keys replaced by the skew a configuration file states under
``assumed``.  Every value comes from ``(seed, shard index)``; any process
regenerates the same frame, so the reference never reads a shard file.
"""

import os

import numpy as np
import pandas as pd

ZONES = 265


def shard_name(index):
    return f"taxi_{index}.bcolzs"


def shard_rows(rows, shards, index):
    per = rows // shards
    return per + (rows % shards if index == shards - 1 else 0)


def _choice(rng, table, rows):
    """Draw ``rows`` values from ``{"values": [...], "p": [...]}``."""
    p = np.asarray(table["p"], dtype=np.float64)
    codes = np.searchsorted(np.cumsum(p / p.sum()), rng.random(rows))
    values = np.asarray(table["values"], dtype=np.int64)
    return values[np.minimum(codes, len(values) - 1)]


def _zones(rng, order, exponent, rows):
    """Zipf over the 265 taxi zones; ``order`` says which zone is busiest."""
    p = 1.0 / np.arange(1, ZONES + 1, dtype=np.float64) ** exponent
    codes = np.searchsorted(np.cumsum(p / p.sum()), rng.random(rows))
    return (order[np.minimum(codes, ZONES - 1)] + 1).astype(np.int64)


def shard_frame(config, seed, index, rows):
    """One shard's rows, in the configuration's nine columns."""
    a = config["assumed"]
    rng = np.random.default_rng([int(seed), int(index)])
    # which zones are busy is a fact of the city, not of the shard
    city = np.random.default_rng([int(seed), 1 << 20])
    pu_order, do_order = city.permutation(ZONES), city.permutation(ZONES)
    mu, sigma = a["trip_distance_lognormal"]
    distance = np.minimum(
        np.round(rng.lognormal(mu, sigma, rows), 2), a["trip_distance_max"]
    )
    payment = _choice(rng, a["payment_type"], rows)
    fare = np.minimum(
        np.rint(250 + distance * 250 + rng.gamma(2.0, 150.0, rows)),
        a["fare_cents_max"],
    ).astype(np.int64)
    lo, hi = a["tip_share_of_fare"]
    tip = np.where(
        payment == 1, np.round(fare / 100.0 * rng.uniform(lo, hi, rows), 2), 0.0
    )
    month = index // config["shards_per_month"]
    month_start = np.datetime64(config["months"][month] + "-01", "ns")
    seconds = rng.integers(0, 28 * 86_400, rows)
    return pd.DataFrame(
        {
            "passenger_count": _choice(rng, a["passenger_count"], rows),
            "fare_amount": fare,   # integer cents: the bit-exactness axis
            "VendorID": _choice(rng, a["VendorID"], rows),
            "payment_type": payment,
            "PULocationID": _zones(rng, pu_order, a["zone_zipf_exponent"], rows),
            "DOLocationID": _zones(rng, do_order, a["zone_zipf_exponent"], rows),
            "trip_distance": distance.astype(np.float32),
            "pickup_ts": month_start + seconds.astype("timedelta64[s]"),
            "tip_amount": tip.astype(np.float64),
        }
    )


def frames(config, seed):
    """Every shard's frame, for the reference."""
    rows, shards = config["rows"], config["shards"]
    return [
        shard_frame(config, seed, i, shard_rows(rows, shards, i))
        for i in range(shards)
    ]


def _write_shard(job):
    """Pool worker (a fresh JAX-free process): write one shard."""
    import sys

    config, seed, index, rows, rootdir = job
    from bqueryd_tpu.storage import native
    from bqueryd_tpu.storage.ctable import ctable

    ctable.fromdataframe(shard_frame(config, seed, index, rows), rootdir)
    if "jax" in sys.modules:
        raise RuntimeError("a shard writer imported jax")
    return native.available()


def build_dataset(config, seed, data_dir):
    """Write the shards in parallel JAX-free processes and move each into
    ``data_dir`` whole.  Returns the shard names."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    rows, shards = config["rows"], config["shards"]
    staging = os.path.join(data_dir, ".staging")
    os.makedirs(staging)
    names = [shard_name(i) for i in range(shards)]
    jobs = [
        (config, seed, i, shard_rows(rows, shards, i), os.path.join(staging, n))
        for i, n in enumerate(names)
    ]
    workers = max(1, min(shards, (os.cpu_count() or 2) - 1))
    with ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context("spawn")
    ) as pool:
        native_served = list(pool.map(_write_shard, jobs))
    if not all(native_served):
        raise RuntimeError("a shard was written without the native codec")
    for name in names:
        os.rename(os.path.join(staging, name), os.path.join(data_dir, name))
    os.rmdir(staging)
    return names
