"""Pallas scan-kernel tile-size sweep on the attached chip.

Builds the 20M-row bench dataset ONCE (saved to /tmp as .npy), then times
``scan_mask_pallas`` for each KB_PALLAS_TILE in a fresh subprocess (the
tile is a trace-time constant). Prints one JSON line per tile.

Usage:
  python tools/tile_sweep.py build          # build + save dataset
  python tools/tile_sweep.py run <tile>     # time one tile size (subprocess)
  python tools/tile_sweep.py sweep          # build if needed, run all tiles
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

DATA = "/tmp/kb_tile_sweep"
TILES = (512, 1024, 2048, 4096, 8192, 16384)
N_KEYS = int(os.environ.get("KB_BENCH_KEYS", 200_000))
REVS = int(os.environ.get("KB_BENCH_REVS", 100))


def build() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import build_dataset, pack_bound

    chunks, rh, rl, tomb = build_dataset(N_KEYS, REVS)
    os.makedirs(DATA, exist_ok=True)
    np.save(f"{DATA}/chunks.npy", chunks)
    np.save(f"{DATA}/rh.npy", rh)
    np.save(f"{DATA}/rl.npy", rl)
    np.save(f"{DATA}/tomb.npy", tomb)
    np.save(f"{DATA}/start.npy", pack_bound(b"/registry/pods/"))
    np.save(f"{DATA}/end.npy", pack_bound(b"/registry/pods0"))
    print(f"[sweep] dataset saved: {len(chunks)} rows", file=sys.stderr)


def run(tile: int) -> None:
    os.environ["KB_PALLAS_TILE"] = str(tile)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    from kubebrain_tpu.ops import scan_pallas as sp
    from kubebrain_tpu.util.jaxcache import use_compile_cache

    use_compile_cache()

    chunks = np.load(f"{DATA}/chunks.npy")
    rh = np.load(f"{DATA}/rh.npy")
    rl = np.load(f"{DATA}/rl.npy")
    tomb = np.load(f"{DATA}/tomb.npy")
    start = np.load(f"{DATA}/start.npy")
    end = np.load(f"{DATA}/end.npy")
    n = len(chunks)
    read_rev = np.uint64(n * 3 // 4)

    revs_u64 = (rh.astype(np.uint64) << np.uint64(32)) | rl.astype(np.uint64)
    keys_t, rh31, rl31, tomb8, n_real = sp.prepare_blocks(chunks, revs_u64, tomb)
    qhi31, qlo31 = sp.split_revs31(np.array([read_rev], dtype=np.uint64))
    s = sp.pack_bound_flipped(start)
    e = sp.pack_bound_flipped(end)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    d = [jax.device_put(jnp.asarray(x), dev) for x in (keys_t, rh31, rl31, tomb8)]
    s_d, e_d = jax.device_put(jnp.asarray(s), dev), jax.device_put(jnp.asarray(e), dev)

    @jax.jit
    def step(kt, a, b, t8, sb, eb):
        m = sp.scan_mask_pallas(kt, a, b, t8, np.int32(n_real), sb, eb,
                                np.int32(0), np.int32(qhi31[0]), np.int32(qlo31[0]),
                                interpret=not on_tpu)
        return jnp.sum(m, dtype=jnp.int32)

    t0 = time.time()
    visible = int(step(*d, s_d, e_d))
    compile_s = time.time() - t0
    lat = []
    for _ in range(7):
        t0 = time.time()
        int(step(*d, s_d, e_d))
        lat.append(time.time() - t0)
    p50 = sorted(lat)[len(lat) // 2]
    best = min(lat)
    print(json.dumps({
        "tile": tile, "rows": n, "visible": visible,
        "p50_ms": round(p50 * 1e3, 2), "best_ms": round(best * 1e3, 2),
        "rows_per_sec": round(n / p50), "compile_s": round(compile_s, 1),
        "device": str(dev),
    }), flush=True)


def sweep() -> None:
    if not os.path.exists(f"{DATA}/chunks.npy"):
        subprocess.run([sys.executable, __file__, "build"], check=True)
    for tile in TILES:
        try:
            r = subprocess.run([sys.executable, __file__, "run", str(tile)],
                               capture_output=True, text=True, timeout=1200)
        except subprocess.TimeoutExpired:
            # one hung tile must not lose the remaining tiles' results
            print(f'{{"tile": {tile}, "error": "timeout"}}', flush=True)
            continue
        out = r.stdout.strip()
        print(out if out else f'{{"tile": {tile}, "error": {json.dumps(r.stderr[-500:])}}}',
              flush=True)


if __name__ == "__main__":
    cmd = sys.argv[1] if len(sys.argv) > 1 else "sweep"
    if cmd == "build":
        build()
    elif cmd == "run":
        run(int(sys.argv[2]))
    else:
        sweep()
