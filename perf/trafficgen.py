"""The one traffic generator: reads a traffic file's parameters and writes
the cell's EDLIO shards from ``--seed``.  A traffic mix is a data file; a
kind of record is a module of its own under ``record_kinds/``
(``Cell.record_kind``), which this generator is handed:

- ``shared_state(spec)``: what every shard shares (class templates), or None;
- ``columns(rng, spec, count, state)``: ``count`` records, one array a field;
- ``batch(columns)``: the ``(features, labels)`` the trainer is handed;
- ``units(spec)``: how many of each work unit one record is.

Every seed gives the same number of records of the same sizes; only the
contents and the order differ."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np

_READ_CHUNK = 8 << 20


def plan(traffic: dict, chips: int) -> dict:
    """Counts that follow from the traffic file for ``chips`` chips."""
    minibatch = int(traffic["batch_per_chip"]) * chips
    records_per_task = minibatch * int(traffic["steps_per_task"])
    records_per_shard = records_per_task * int(traffic["tasks_per_shard"])
    return {
        "minibatch_size": minibatch,
        "records_per_task": records_per_task,
        "records_per_shard": records_per_shard,
        "num_shards": int(traffic["num_shards"]),
        "num_records": records_per_shard * int(traffic["num_shards"]),
        "steps_per_interval": int(traffic["steps_per_task"])
        * int(traffic["tasks_per_interval"]),
    }


def units_per_record(kind, traffic: dict, unit: str) -> int:
    """How many of the configuration's work units one record is."""
    units = kind.units(traffic["records"])
    if unit not in units:
        raise ValueError(
            f"records of kind {traffic['records']['kind']!r} count no "
            f"{unit!r}: have {sorted(units)}"
        )
    return int(units[unit])


def one_batch(kind, traffic: dict, rows: int, seed: int, stream: int = 0):
    """One seeded ``(features, labels)`` batch of ``rows`` records, as the
    zoo's parse functions hand it to the trainer.  ``stream`` 0 is shard 0's
    (``generate``); another stream gives records no shard holds."""
    spec = traffic["records"]
    return kind.batch(
        kind.columns(np.random.default_rng([seed, stream]), spec, rows)
    )


def generate(kind, traffic: dict, chips: int, seed: int, out_dir: str) -> dict:
    """Write the shards under ``out_dir`` (replacing what another seed left
    there), read each once so the page cache holds it, and return the plan
    with the data directory."""
    from elasticdl_tpu.data import recordio
    from elasticdl_tpu.data.reader import encode_example

    counts = plan(traffic, chips)
    stamp = {"traffic": traffic, "chips": chips, "seed": seed}
    stamp_path = os.path.join(out_dir, "stamp.json")
    data_dir = os.path.join(out_dir, "shards")
    fresh = True
    if os.path.exists(stamp_path):
        with open(stamp_path) as f:
            fresh = json.load(f) != stamp
    if fresh:
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(data_dir)
        spec = traffic["records"]
        state = kind.shared_state(spec)
        for shard in range(counts["num_shards"]):
            rng = np.random.default_rng([seed, shard])
            columns = kind.columns(
                rng, spec, counts["records_per_shard"], state
            )
            path = os.path.join(data_dir, f"shard-{shard:03d}.edlio")
            with recordio.Writer(path) as writer:
                for row in range(counts["records_per_shard"]):
                    writer.write(
                        encode_example({k: v[row] for k, v in columns.items()})
                    )
        with open(stamp_path, "w") as f:
            json.dump(stamp, f)
    nbytes = 0
    for name in sorted(os.listdir(data_dir)):
        with open(os.path.join(data_dir, name), "rb") as f:
            while chunk := f.read(_READ_CHUNK):
                nbytes += len(chunk)
    return {**counts, "data_dir": data_dir, "bytes": nbytes, "generated": fresh}
