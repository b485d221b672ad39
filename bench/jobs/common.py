"""What the job kinds share: the store written through the program's writer."""

from __future__ import annotations

import shutil
import tempfile

import numpy as np

STORE_CHUNK = 8192


def write_store(db: np.ndarray):
    """The generated rows, written through the program's own ``StoreWriter``
    with its default layout. Returns (store, directory)."""
    from repro.data.store import StoreWriter

    path = tempfile.mkdtemp(prefix="bench_store_")
    writer = StoreWriter(path, db.shape[1])
    for s in range(0, db.shape[0], STORE_CHUNK):
        writer.append_dense(db[s:s + STORE_CHUNK])
    return writer.close(), path


def remove(path: str | None) -> None:
    if path:
        shutil.rmtree(path, ignore_errors=True)


def mining_config(config: dict):
    """The program's mining settings for this deployment, all else default."""
    from repro.core.apriori import AprioriConfig

    m = config["mining"]
    return AprioriConfig(min_support=m["min_support"], max_k=m["max_k"])


def compile_rulebook(result, config: dict):
    from repro.serving.rulebook import compile_rulebook as compile_

    m = config["mining"]
    return compile_(result, min_confidence=m["min_confidence"], score=m["rule_score"],
                    num_items=config["quest"]["N"])
