#!/usr/bin/env python3
"""Writes a configuration's start state straight into the native store's
data directory, so that a run's set-up does not pay the wire (150,000
creates through the front took 246.8 s on the chip host, PERF.md section 5).

This is the one file of the benchmark that imports ``kubebrain_tpu``; its
only interface to the server under test is the data directory, which the
server then opens and rebuilds its mirror from. It runs as a process of its
own and never imports JAX.

    python benchmarks/loader.py <config.json> <seed> <data_dir>
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
GROUP = 1024   # ops per commit group


def load(config: dict, seed: int, data_dir: str) -> dict:
    sys.path.insert(0, os.path.dirname(HERE))
    sys.path.insert(0, HERE)
    from state import State

    from kubebrain_tpu.backend import Backend
    from kubebrain_tpu.storage import new_storage

    t0 = time.monotonic()
    state = State(config, seed)
    store = new_storage("native", data_dir=data_dir)
    backend = Backend(store)
    done = 0
    try:
        group: list = []

        def flush():
            nonlocal done
            for op, got in zip(group, backend.write_batch([o for o, _ in group])):
                want = op[1]
                rev = got[0] if isinstance(got, tuple) else got
                if rev != want:
                    raise RuntimeError(
                        f"loader: {op[0][0]} of {op[0][1]!r} gave {got!r}, "
                        f"the start state has revision {want}")
            done += len(group)
            group.clear()

        for verb, table, i, ver, guard in state.start_ops():
            key = table.key(i)
            want = done + len(group) + 1
            if verb == "create":
                op = ("create", key, state.value(table, i, ver), None, 0)
            elif verb == "update":
                op = ("update", key, state.value(table, i, ver), guard, None, 0)
            else:
                op = ("delete", key, guard)
            group.append((op, want))
            if len(group) >= GROUP:
                flush()
        flush()
        if backend.current_revision() != state.head_revision:
            raise RuntimeError(
                f"loader: store at revision {backend.current_revision()}, "
                f"the start state ends at {state.head_revision}")
    finally:
        backend.close()
        store.close()
    if "jax" in sys.modules:
        raise RuntimeError("loader: imported jax")
    return {"rows": done, "seconds": time.monotonic() - t0,
            "bytes": sum(os.path.getsize(os.path.join(data_dir, f))
                         for f in os.listdir(data_dir))}


def main(argv: list[str]) -> int:
    config_path, seed, data_dir = argv
    with open(config_path) as f:
        config = json.load(f)
    os.makedirs(data_dir, exist_ok=True)
    print(json.dumps(load(config, int(seed), data_dir)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
