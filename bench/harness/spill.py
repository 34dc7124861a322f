"""Client models on disk, so that a check holds a fixed number of them in
host memory whatever the size of the cohort.

A store writes each client to a file of its own in its directory: the
leaves of its parameter tree back to back, in tree order. `get` reads one
client back whole; with `mapped=True` its leaves are read-only memory
maps of the file, which a fold reads leaf by leaf.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Hashable

import jax
import numpy as np


class ClientStore:
    def __init__(self, directory):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._layout: Dict[Hashable, tuple] = {}

    def _path(self, key) -> Path:
        return self.dir / f"{key}.bin"

    def put(self, key, tree) -> None:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        layout, offset = [], 0
        with open(self._path(key), "wb") as f:
            for x in leaves:
                x = np.ascontiguousarray(x)
                x.tofile(f)
                layout.append((x.shape, x.dtype, offset, x.nbytes))
                offset += x.nbytes
        self._layout[key] = (treedef, layout)

    def get(self, key, mapped: bool = False):
        treedef, layout = self._layout[key]
        path = self._path(key)
        buf = (np.memmap(path, np.uint8, mode="r") if mapped
               else np.fromfile(path, np.uint8))
        leaves = [buf[o:o + n].view(dt).reshape(shape)
                  for shape, dt, o, n in layout]
        return jax.tree_util.tree_unflatten(treedef, leaves)
