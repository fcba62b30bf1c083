"""kblint self-tests: each rule catches its target pattern, stays quiet on
clean code, and honors the suppression syntax."""

import os
import subprocess
import sys

import pytest

from tools.kblint import rules  # noqa: F401  -- registers the rules
from tools.kblint.core import RULES, lint_source

EP = "kubebrain_tpu/endpoint/x.py"
SRV_ETCD = "kubebrain_tpu/server/etcd/x.py"
OPS = "kubebrain_tpu/ops/x.py"
ANY = "kubebrain_tpu/backend/x.py"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ids(src, relpath):
    return [f.rule_id for f in lint_source(src, relpath)]


# ------------------------------------------------------------------- KB101
def test_kb101_flags_sleep_in_async():
    src = "import time\nasync def f():\n    time.sleep(1)\n"
    assert ids(src, EP) == ["KB101"]


def test_kb101_flags_subprocess_in_async():
    src = "import subprocess\nasync def f():\n    subprocess.Popen(['x'])\n"
    assert ids(src, EP) == ["KB101"]


def test_kb101_ignores_executor_thunk():
    # a nested sync def is an executor thunk, not coroutine-body code
    src = (
        "import time\n"
        "async def f(loop):\n"
        "    def blocking():\n"
        "        time.sleep(1)\n"
        "    await loop.run_in_executor(None, blocking)\n"
    )
    assert ids(src, EP) == []


def test_kb101_scoped_to_endpoint_and_server():
    src = "import time\nasync def f():\n    time.sleep(1)\n"
    assert ids(src, ANY) == []


def test_kb101_sees_nested_async_def():
    src = (
        "import time\n"
        "async def outer():\n"
        "    async def inner():\n"
        "        time.sleep(1)\n"
        "    await inner()\n"
    )
    assert ids(src, EP) == ["KB101"]


# ------------------------------------------------------------------- KB102
def test_kb102_flags_jax_under_lock():
    src = (
        "import jax\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        jax.device_put(1)\n"
    )
    assert ids(src, ANY) == ["KB102"]


def test_kb102_flags_sleep_under_lock():
    src = "import time\ndef f(self):\n    with self._mlock:\n        time.sleep(1)\n"
    assert ids(src, ANY) == ["KB102"]


def test_kb102_flags_rpc_under_lock():
    src = (
        "import urllib.request\n"
        "def f(self):\n"
        "    with self.lock:\n"
        "        urllib.request.urlopen('http://x')\n"
    )
    assert ids(src, ANY) == ["KB102"]


def test_kb102_ignores_non_lock_context():
    src = "import time\ndef f(self):\n    with open('x') as fh:\n        time.sleep(1)\n"
    assert ids(src, ANY) == []


def test_kb102_ignores_callback_defined_under_lock():
    src = (
        "import jax\n"
        "def f(self):\n"
        "    with self._lock:\n"
        "        def later():\n"
        "            jax.device_put(1)\n"
        "        self.cb = later\n"
    )
    assert ids(src, ANY) == []


# ------------------------------------------------------------------- KB103
def test_kb103_flags_bare_except():
    src = "try:\n    x = 1\nexcept:\n    pass\n"
    assert ids(src, ANY) == ["KB103"]


def test_kb103_allows_typed_except():
    src = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    assert ids(src, ANY) == []


# ------------------------------------------------------------------- KB104
@pytest.mark.parametrize("decorator", [
    "@jax.jit",
    "@jit",
    "@partial(jax.jit, static_argnums=0)",
    "@jax.jit(static_argnums=0)",
])
def test_kb104_flags_device_get_in_jit(decorator):
    src = (
        "import jax\nfrom functools import partial\nfrom jax import jit\n"
        f"{decorator}\n"
        "def kernel(x):\n"
        "    return jax.device_get(x)\n"
    )
    assert ids(src, OPS) == ["KB104"]


def test_kb104_flags_block_until_ready_in_jit():
    src = "import jax\n@jax.jit\ndef kernel(x):\n    return x.block_until_ready()\n"
    assert ids(src, OPS) == ["KB104"]


def test_kb104_ignores_unjitted_and_out_of_ops():
    src = "import jax\ndef driver(x):\n    return jax.device_get(x)\n"
    assert ids(src, OPS) == []
    jitted = "import jax\n@jax.jit\ndef kernel(x):\n    return jax.device_get(x)\n"
    assert ids(jitted, ANY) == []


# ------------------------------------------------------------------- KB105
def test_kb105_flags_raw_revision_arithmetic():
    assert ids("def f(rev):\n    return rev + 1\n", SRV_ETCD) == ["KB105"]
    assert ids("def f(creq):\n    r = -int(creq.start_revision)\n", SRV_ETCD) == ["KB105"]
    assert ids("def f(rev):\n    rev += 1\n    return rev\n", SRV_ETCD) == ["KB105"]


def test_kb105_allows_helpers_and_encoding():
    src = (
        "from ..service.revision import next_revision\n"
        "def f(rev):\n"
        "    return next_revision(rev)\n"
    )
    assert ids(src, SRV_ETCD) == []
    # serializing a revision into a frame is encoding, not arithmetic
    enc = "def f(rev):\n    return b'HDR' + rev.to_bytes(8, 'big')\n"
    assert ids(enc, SRV_ETCD) == []


def test_kb105_scoped_to_server_etcd():
    assert ids("def f(rev):\n    return rev + 1\n", ANY) == []


def test_kb105_ignores_non_revision_arithmetic():
    assert ids("def f(n):\n    return n + 1\n", SRV_ETCD) == []
    assert ids("def f(prev):\n    return prev + 1\n", SRV_ETCD) == []


# ------------------------------------------------------------------- KB106
def test_kb106_flags_direct_backend_scan_calls():
    for entry in ("list_", "count", "list_wire", "list_by_stream"):
        src = f"def f(self, s, e):\n    return self.backend.{entry}(s, e)\n"
        assert ids(src, SRV_ETCD) == ["KB106"], entry
        assert ids(src, EP) == ["KB106"], entry


def test_kb106_flags_direct_scanner_calls():
    src = "def f(self, s, e):\n    return self.backend.scanner.range_(s, e, 0)\n"
    assert ids(src, SRV_ETCD) == ["KB106"]


def test_kb106_allows_scheduler_and_non_scan_calls():
    clean = (
        "def f(self, s, e):\n"
        "    kv = self.backend.get(s)\n"
        "    rev = self.backend.current_revision()\n"
        "    parts = self.backend.get_partitions(s, e)\n"
        "    return self.limiter.list_(s, e)\n"
    )
    assert ids(clean, SRV_ETCD) == []
    via_ensure = (
        "from kubebrain_tpu.sched import ensure_scheduler\n"
        "def f(self, s, e):\n"
        "    return ensure_scheduler(self.backend).list_by_stream(s, e)\n"
    )
    assert ids(via_ensure, EP) == []


def test_kb106_scoped_to_service_layer():
    # the scheduler itself and the backend core ARE the scan path
    src = "def f(self, s, e):\n    return self.backend.list_(s, e)\n"
    assert ids(src, ANY) == []
    assert ids(src, "kubebrain_tpu/sched/scheduler.py") == []
    assert ids(src, "kubebrain_tpu/server/brain/server.py") == []


def test_kb106_suppressible():
    src = (
        "def f(self, s, e):\n"
        "    return self.backend.list_(s, e)  # kblint: disable=KB106 -- test\n"
    )
    assert ids(src, SRV_ETCD) == []


def test_kb106_flags_direct_backend_write_calls():
    # writes are funneled like reads (docs/writes.md): the service layer
    # reaches create/update/delete only through the scheduler's write lanes
    for entry, args in (("create", "k, v"), ("update", "k, v, 3"),
                        ("delete", "k")):
        src = f"def f(self, k, v):\n    return self.backend.{entry}({args})\n"
        assert ids(src, SRV_ETCD) == ["KB106"], entry
        assert ids(src, EP) == ["KB106"], entry
    # the scheduler's own write entries are the sanctioned path
    clean = (
        "def f(self, k, v):\n"
        "    self.limiter.create(k, v)\n"
        "    self.limiter.update(k, v, 3)\n"
        "    return self.limiter.delete(k)\n"
    )
    assert ids(clean, SRV_ETCD) == []
    # unrelated receivers named neither backend nor scanner stay clean
    assert ids("def f(self, k):\n    self.watchers.delete(k)\n",
               SRV_ETCD) == []


def test_kb106_flags_laundered_write_batch_call():
    # write_batch is the group-commit executor itself: flagged on ANY
    # receiver, so aliasing the backend can't launder a direct group
    # commit past the admission queue
    laundered = (
        "def f(self, ops):\n"
        "    b = self.backend\n"
        "    return b.write_batch(ops)\n"
    )
    assert ids(laundered, SRV_ETCD) == ["KB106"]
    assert ids(laundered, EP) == ["KB106"]
    direct = "def f(self, ops):\n    return self.backend.write_batch(ops)\n"
    assert ids(direct, SRV_ETCD) == ["KB106"]
    # out of the service layer the backend core and scheduler ARE the path
    assert ids(direct, "kubebrain_tpu/sched/scheduler.py") == []
    assert ids(direct, ANY) == []


# ------------------------------------------------------------- suppressions
def test_suppression_on_flagged_line():
    src = "import time\nasync def f():\n    time.sleep(1)  # kblint: disable=KB101 -- test\n"
    assert ids(src, EP) == []


def test_suppression_on_comment_line_above():
    src = (
        "import time\n"
        "async def f():\n"
        "    # kblint: disable=KB101 -- test\n"
        "    time.sleep(1)\n"
    )
    assert ids(src, EP) == []


def test_suppression_on_with_header_covers_block():
    src = (
        "import jax\n"
        "def f(self):\n"
        "    with self._lock:  # kblint: disable=KB102 -- mirror publish\n"
        "        jax.device_put(1)\n"
        "        jax.device_put(2)\n"
    )
    assert ids(src, ANY) == []


def test_kb102_async_with_flagged_and_header_suppressible():
    src = (
        "import jax\n"
        "async def f(self):\n"
        "    async with self._lock:\n"
        "        jax.device_put(1)\n"
    )
    assert ids(src, ANY) == ["KB102"]
    sup = src.replace(
        "async with self._lock:",
        "async with self._lock:  # kblint: disable=KB102 -- test",
    )
    assert ids(sup, ANY) == []


def test_file_level_suppression():
    src = "# kblint: disable-file=KB103\ntry:\n    x = 1\nexcept:\n    pass\n"
    assert ids(src, ANY) == []


def test_wrong_rule_suppression_does_not_mask():
    src = "import time\nasync def f():\n    time.sleep(1)  # kblint: disable=KB103\n"
    assert ids(src, EP) == ["KB101"]


def test_trailing_code_pragma_does_not_leak_to_next_line():
    src = (
        "import time\n"
        "async def f():\n"
        "    x = 1  # kblint: disable=KB101\n"
        "    time.sleep(1)\n"
    )
    assert ids(src, EP) == ["KB101"]


# ------------------------------------------------------------------- KB107
def test_kb107_flags_print_on_serving_path():
    src = "def f(x):\n    print(x)\n"
    assert ids(src, SRV_ETCD) == ["KB107"]
    assert ids(src, EP) == ["KB107"]
    assert ids(src, "kubebrain_tpu/sched/x.py") == ["KB107"]


def test_kb107_flags_raw_time_time_latency():
    assert ids(
        "import time\ndef f(t0):\n    return time.time() - t0\n", SRV_ETCD
    ) == ["KB107"]
    assert ids(
        "import time as _time\ndef f(t0):\n    d = _time.time() - t0\n", EP
    ) == ["KB107"]
    # either side of the subtraction counts
    assert ids(
        "import time\ndef f(t1):\n    return t1 - time.time()\n", SRV_ETCD
    ) == ["KB107"]


def test_kb107_allows_monotonic_and_non_latency_time():
    # monotonic()/perf_counter() deltas are the correct clock — allowed
    assert ids(
        "import time\ndef f(t0):\n    return time.monotonic() - t0\n", SRV_ETCD
    ) == []
    # time.time() not in a subtraction (timestamps, dir names) is fine
    assert ids(
        "import time\ndef f():\n    return f'/tmp/p-{int(time.time())}'\n",
        SRV_ETCD,
    ) == []
    assert ids("import time\ndef f(rec):\n    return rec.expired(time.time())\n",
               SRV_ETCD) == []


def test_kb107_scoped_and_suppressible():
    src = "def f(x):\n    print(x)\n"
    assert ids(src, ANY) == []  # backend/ etc. are out of scope
    sup = "def f(x):\n    print(x)  # kblint: disable=KB107\n"
    assert ids(sup, SRV_ETCD) == []


# ------------------------------------------------------------------- KB108
def test_kb108_flags_wall_clock_ttl_add():
    src = "import time\ndef f(ttl):\n    return time.time() + ttl\n"
    assert ids(src, ANY) == ["KB108"]  # backend/ is serving path
    assert ids(src, "kubebrain_tpu/lease/registry.py") == ["KB108"]


def test_kb108_flags_wall_clock_deadline_sub():
    # remaining-TTL math against wall clock (backend/ avoids KB107 overlap)
    src = "import time\ndef f(lease):\n    return lease.expires_at - time.time()\n"
    assert ids(src, ANY) == ["KB108"]


def test_kb108_flags_deadline_comparison():
    src = "import time\ndef f(deadline):\n    return time.time() > deadline\n"
    assert ids(src, ANY) == ["KB108"]


def test_kb108_flags_ttlish_assignment_target():
    # no ttl-ish name in the expression, but the target is one
    src = "import time\ndef f(self):\n    self.deadline = time.time() + 30\n"
    assert ids(src, ANY) == ["KB108"]
    # ...and it is reported exactly once when BOTH sides are ttl-ish
    src2 = "import time\ndef f(self, ttl):\n    self.deadline = time.time() + ttl\n"
    assert ids(src2, ANY) == ["KB108"]


def test_kb108_allows_lease_clock_and_non_ttl_uses():
    # lease/clock.py is the one module allowed to do the conversion
    src = "import time\ndef f(ttl):\n    return time.time() + ttl\n"
    assert ids(src, "kubebrain_tpu/lease/clock.py") == []
    # arithmetic without a TTL-ish name is not deadline math
    assert ids("import time\ndef f():\n    return time.time() + 1\n", ANY) == []
    # monotonic deadline math is the correct form
    assert ids("import time\ndef f(ttl):\n    return time.monotonic() + ttl\n",
               ANY) == []
    # wall clock passed as a plain argument (election records) is fine
    assert ids("import time\ndef f(rec):\n    return rec.expired(time.time())\n",
               ANY) == []


def test_kb108_scoped_and_suppressible():
    src = "import time\ndef f(ttl):\n    return time.time() + ttl\n"
    assert ids(src, "kubebrain_tpu/client.py") == []  # client is off-path
    assert ids(src, OPS) == []
    sup = ("import time\ndef f(ttl):\n"
           "    return time.time() + ttl  # kblint: disable=KB108\n")
    assert ids(sup, ANY) == []


# ------------------------------------------------------------------- KB109
TPU_ENG = "kubebrain_tpu/storage/tpu/x.py"
SCHED = "kubebrain_tpu/sched/x.py"


def test_kb109_flags_stray_kernel_call_in_engine_layer():
    src = ("from kubebrain_tpu.ops.scan_pallas import scan_mask_pallas\n"
           "def fast_count(kt, a, b, t, n, s, e):\n"
           "    return scan_mask_pallas(kt, a, b, t, n, s, e, 0, 0, 0).sum()\n")
    assert ids(src, TPU_ENG) == ["KB109"]
    assert ids(src, SCHED) == ["KB109"]


def test_kb109_flags_stray_dispatch_inside_class_method():
    # TpuScanner methods are exactly where the rule's target code lives —
    # class bodies must be descended into, not skipped at the header
    src = ("from kubebrain_tpu.ops.scan_pallas import scan_mask_pallas\n"
           "class Engine:\n"
           "    def sneaky(self, *a):\n"
           "        return scan_mask_pallas(*a)\n")
    assert ids(src, TPU_ENG) == ["KB109"]
    ok = ("from kubebrain_tpu.ops.scan_pallas import scan_mask_pallas_q\n"
          "class Engine:\n"
          "    def _dev_mask_batch(self, *a):\n"
          "        return scan_mask_pallas_q(*a)\n")
    assert ids(ok, TPU_ENG) == []


def test_kb109_flags_wrapped_kernel_reference():
    # vmap/partial around a kernel outside an assembly point is the same
    # bypass as calling it directly
    src = ("import jax\n"
           "from kubebrain_tpu.ops.scan_pallas import visibility_mask_batch_cached_q\n"
           "def sneaky(args):\n"
           "    return jax.vmap(visibility_mask_batch_cached_q)(*args)\n")
    assert ids(src, TPU_ENG) == ["KB109"]


def test_kb109_allows_assembly_points_and_wrappers():
    src = ("from kubebrain_tpu.ops.scan_pallas import scan_mask_pallas_q\n"
           "def _vis_batch_pallas_q(kt, s):\n"
           "    f = lambda x: scan_mask_pallas_q(x, s)\n"
           "    return f(kt)\n"
           "class E:\n"
           "    def _dev_mask(self, m, s, e, r):\n"
           "        return _vis_batch_pallas_q(m, s)\n"
           "    def _dev_mask_batch(self, m, specs):\n"
           "        return _vis_batch_q(m, specs)\n"
           "    def scan_batch(self, qs):\n"
           "        return self._dev_mask_batch(None, qs)\n")
    assert ids(src, TPU_ENG) == []


def test_kb109_flags_the_fused_read_outside_the_assembly_points():
    # `_vis_rows` is a read's one device call (packed query in, counts and
    # indices out): launched anywhere but the assembly points, it forks the
    # query packing and the kernel selection they keep
    src = ("class E:\n"
           "    def range_(self, cols, q):\n"
           "        return _vis_rows(cols, q, kernel='jnp', n=0, size=64)\n")
    assert ids(src, TPU_ENG) == ["KB109"]
    ok = ("class E:\n"
          "    def _dev_mask(self, cols, q, size):\n"
          "        return _vis_rows(cols, q, kernel='jnp', n=0, size=size)\n"
          "    def _dev_mask_batch(self, cols, q, size):\n"
          "        return _vis_rows(cols, q, kernel='jnp', n=0, size=size)\n")
    assert ids(ok, TPU_ENG) == []
    # and what it returns is device data: pulled outside `_host_pull`, it
    # is an unmetered transfer as well
    leak = ("import numpy as np\n"
            "def leak(cols, q):\n"
            "    return np.asarray(_vis_rows(cols, q, kernel='jnp', n=0, size=8))\n")
    assert ids(leak, TPU_ENG) == ["KB109", "KB111"]


def test_kb109_scoped_and_suppressible():
    src = ("from kubebrain_tpu.ops.scan_pallas import scan_mask_pallas\n"
           "def f(*a):\n"
           "    return scan_mask_pallas(*a)\n")
    assert ids(src, ANY) == []  # ops/tests layers stay free to call kernels
    sup = ("from kubebrain_tpu.ops.scan_pallas import scan_mask_pallas\n"
           "def f(*a):\n"
           "    return scan_mask_pallas(*a)  # kblint: disable=KB109\n")
    assert ids(sup, TPU_ENG) == []


# ------------------------------------------------------------------- KB110
WORKLOAD = "kubebrain_tpu/workload/x.py"


def test_kb110_flags_module_level_random():
    src = "import random\ndef jitter():\n    return random.random()\n"
    assert ids(src, WORKLOAD) == ["KB110"]
    src2 = "import random\ndef pick(xs):\n    return random.choice(xs)\n"
    assert ids(src2, WORKLOAD) == ["KB110"]


def test_kb110_flags_np_random_and_unseeded_ctor():
    src = "import numpy as np\ndef f():\n    return np.random.randint(10)\n"
    assert ids(src, WORKLOAD) == ["KB110"]
    src2 = "import random\ndef f():\n    return random.Random()\n"
    assert ids(src2, WORKLOAD) == ["KB110"]


def test_kb110_flags_time_time_in_schedule_path():
    src = "import time\ndef stamp():\n    return time.time()\n"
    assert ids(src, WORKLOAD) == ["KB110"]


def test_kb110_allows_seeded_rng_and_monotonic():
    src = ("import random\nimport time\n"
           "def gen(seed):\n"
           "    rng = random.Random(seed)\n"
           "    t0 = time.monotonic()\n"
           "    return rng.random() + rng.expovariate(2.0) + t0\n")
    assert ids(src, WORKLOAD) == []
    src2 = ("import numpy as np\n"
            "def gen(seed):\n"
            "    return np.random.default_rng(seed).integers(10)\n")
    assert ids(src2, WORKLOAD) == []


def test_kb110_sees_through_import_aliases():
    # the holes an aliased import would open must stay closed (same
    # diligence _is_time_time applies to `import time as _time`)
    src = "import random as r\ndef f():\n    return r.random()\n"
    assert ids(src, WORKLOAD) == ["KB110"]
    src2 = "from random import random\ndef f():\n    return random()\n"
    assert ids(src2, WORKLOAD) == ["KB110"]
    src3 = ("import numpy.random\n"
            "def f():\n    return numpy.random.randint(3)\n")
    assert ids(src3, WORKLOAD) == ["KB110"]
    # a plain dotted import binds the TOP-LEVEL package: seeded ctors and
    # non-RNG numpy calls under it must not be mangled into false positives
    src3b = ("import numpy.random\n"
             "def f(seed, xs):\n"
             "    return numpy.random.default_rng(seed), numpy.array(xs)\n")
    assert ids(src3b, WORKLOAD) == []
    # aliased but properly seeded stays legal
    src4 = ("from random import Random\n"
            "def f(seed):\n    return Random(seed).random()\n")
    assert ids(src4, WORKLOAD) == []
    src5 = "from random import Random\ndef f():\n    return Random()\n"
    assert ids(src5, WORKLOAD) == ["KB110"]


def test_kb110_scoped_and_suppressible():
    src = "import random\ndef f():\n    return random.random()\n"
    assert ids(src, ANY) == []  # only workload/ carries the replay contract
    sup = ("import random\n"
           "def f():\n"
           "    return random.random()  # kblint: disable=KB110\n")
    assert ids(sup, WORKLOAD) == []


# ------------------------------------------------------------------- KB111
TPU = "kubebrain_tpu/storage/tpu/x.py"


def test_kb111_flags_device_get_outside_named_points():
    src = "import jax\ndef leak(mask):\n    return jax.device_get(mask)\n"
    assert ids(src, TPU) == ["KB111"]


def test_kb111_flags_asarray_of_dev_column():
    src = ("import numpy as np\n"
           "def leak(mirror):\n"
           "    return np.asarray(mirror.keys_dev)\n")
    assert ids(src, TPU) == ["KB111"]


def test_kb111_flags_asarray_of_kernel_result():
    src = ("import numpy as np\n"
           "def leak(m, nv):\n"
           "    return np.asarray(_victim_part_counts(m, nv))\n")
    assert ids(src, TPU) == ["KB111"]
    # the compaction survivor-index producer is device-taint too
    src1s = ("import numpy as np\n"
             "def leak(m, nv):\n"
             "    return np.asarray(_part_survivor_indices(m, nv, size=8))\n")
    assert ids(src1s, TPU) == ["KB111"]
    # a scan-kernel reference outside the assembly points trips BOTH
    # disciplines: KB109 (stray dispatch) and KB111 (unmetered transfer)
    src1b = ("import numpy as np\n"
             "def leak(m, c):\n"
             "    return np.asarray(_vis_batch(m, c))\n")
    assert ids(src1b, TPU) == ["KB109", "KB111"]
    src2 = ("import numpy as np\n"
            "def leak(mask):\n"
            "    return np.array(_part_indices_of_mask(mask, size=8))\n")
    assert ids(src2, TPU) == ["KB111"]


def test_kb111_allows_named_materialization_points():
    src = ("import jax\nimport numpy as np\n"
           "def _host_pull(x):\n"
           "    return np.asarray(x)\n"
           "def _pallas_ttl8(self, mirror, npad):\n"
           "    return jax.device_get(mirror.ttl_dev)\n"
           "def _pull_victim_indices(self, mask_dev, mirror):\n"
           "    return np.asarray(_part_survivor_indices(mask_dev, 1, size=4))\n")
    assert ids(src, TPU) == []
    # the OLD compact transfer funnel is no longer a named point: the
    # shard-local `_pull_victim_indices` replaced it (docs/compaction.md)
    old = ("import numpy as np\n"
           "def _pull_victim_mask(self, mask_dev, mirror):\n"
           "    return np.asarray(mask_dev)\n")
    assert ids(old, TPU) == ["KB111"]


def test_kb111_ignores_host_array_conversions():
    # np.asarray on host-side mirror columns is a no-op, not a transfer
    src = ("import numpy as np\n"
           "def f(mirror):\n"
           "    return np.asarray(mirror.revs_host, dtype=np.uint64)\n")
    assert ids(src, TPU) == []


def test_kb111_scoped_to_storage_tpu_and_suppressible():
    src = "import jax\ndef f(x):\n    return jax.device_get(x)\n"
    assert ids(src, ANY) == []
    sup = ("import jax\n"
           "def f(x):\n"
           "    return jax.device_get(x)  # kblint: disable=KB111\n")
    assert ids(sup, TPU) == []


def test_kb106_covers_batched_entry_points():
    src = "def f(backend, qs):\n    return backend.list_batch(qs)\n"
    assert ids(src, SRV_ETCD) == ["KB106"]
    src2 = "def f(scanner, qs):\n    return scanner.scan_batch(qs)\n"
    assert ids(src2, EP) == ["KB106"]


# ------------------------------------------------------------------- KB116
def test_kb116_flags_decode_primitive_outside_funnels():
    # a stray decode_rows materializes the full-width key column on the
    # host outside the visible-row sizing — the unmetered decode path
    src = ("def leak(mirror, rows):\n"
           "    return mirror.encoding.decode_rows(rows, None)\n")
    assert ids(src, TPU) == ["KB116"]
    src2 = ("def peek(mirror, p, i):\n"
            "    return mirror.encoding.decode_one(mirror.keys_host[p, i], 3)\n")
    assert ids(src2, TPU) == ["KB116"]


def test_kb116_flags_decoded_keys_outside_materialization_paths():
    src = ("def dump_all(mirror, p, nv):\n"
           "    return mirror.decoded_keys(p, range(nv))\n")
    assert ids(src, TPU) == ["KB116"]


def test_kb116_allows_the_funnel_chain():
    src = ("import numpy as np\n"
           "def decoded_keys(self, p, rows):\n"
           "    return self.encoding.decode_rows(self.keys_host[p][rows], None)\n"
           "def user_key(self, p, i):\n"
           "    return self.encoding.decode_one(self.keys_host[p, i], 0)\n"
           "def materialize(self, p, rows):\n"
           "    return self.decoded_keys(p, rows)\n"
           "def flat_arrays(self):\n"
           "    return self.decoded_keys(0, [])\n"
           "def merge_partitions_incremental(mirror, p):\n"
           "    return mirror.decoded_keys(p, [])\n"
           "def _compact_victim_rows(self, mirror, p, rows):\n"
           "    return mirror.decoded_keys(p, rows)\n")
    assert ids(src, TPU) == []


def test_kb116_flags_whole_partition_decode_in_compact():
    """The pre-stored-domain compact shape — decode EVERY surviving row of
    every partition (`decoded_keys(p, np.arange(nv))` straight from
    ``compact``) — must now be flagged: since the stored-domain survivor
    merge (docs/compaction.md) the only decode compaction may perform is
    the victim-only ``_compact_victim_rows`` funnel."""
    src = ("import numpy as np\n"
           "def compact(self, start, end, rev):\n"
           "    mirror = self._mirror\n"
           "    return mirror.decoded_keys(0, np.arange(10))\n")
    assert ids(src, TPU) == ["KB116"]


def test_kb116_scoped_to_storage_tpu_and_exempts_encode_py():
    src = "def f(enc, rows):\n    return enc.decode_rows(rows, None)\n"
    assert ids(src, ANY) == []                       # outside storage/tpu/
    assert ids(src, "kubebrain_tpu/storage/tpu/encode.py") == []


# ------------------------------------------------------------------- KB117
def test_kb117_flags_raw_bound_packing_outside_dispatch():
    # packing a bound outside _bound_rows hands a RAW-domain bound to
    # whatever kernel compare it reaches — wrong by construction against
    # an encoded mirror
    src = ("from kubebrain_tpu.ops import keys as keyops\n"
           "def my_query(self, start):\n"
           "    return keyops.pack_one(start, self._kw)\n")
    assert ids(src, TPU) == ["KB117"]


def test_kb117_flags_encoded_bound_helper_outside_dispatch():
    src = ("def my_query(self, mirror, start):\n"
           "    return mirror.encoding.encode_start_bound(start)\n")
    assert ids(src, TPU) == ["KB117"]
    src2 = ("def probe(self, mirror, k):\n"
            "    return mirror.encoding.encode_probe(k)\n")
    assert ids(src2, TPU) == ["KB117"]


def test_kb117_allows_the_dispatch_funnels():
    src = ("from kubebrain_tpu.ops import keys as keyops\n"
           "def _bound_rows(self, mirror, start, end):\n"
           "    if mirror.encoding is not None:\n"
           "        return mirror.encoding.encode_start_bound(start)\n"
           "    return keyops.pack_one(start, self._kw)\n"
           "def _host_visible_batch(self, mirror, ukeys, rev):\n"
           "    if mirror.encoding is not None:\n"
           "        return [mirror.encoding.encode_probe(k) for k in ukeys]\n"
           "    return [keyops.pack_one(k, self._kw) for k in ukeys]\n")
    assert ids(src, TPU) == []


def test_kb117_scoped_to_storage_tpu():
    src = ("from kubebrain_tpu.ops import keys as keyops\n"
           "def f(w):\n"
           "    return keyops.pack_one(b'/registry/', w)\n")
    assert ids(src, ANY) == []                       # e.g. parallel/step.py
    assert ids(src, "kubebrain_tpu/storage/tpu/encode.py") == []


# ------------------------------------------------------------------- KB127
def test_kb127_flags_fanout_kernel_outside_funnels():
    src = ("from kubebrain_tpu.ops.fanout import fanout_mask_range\n"
           "def stream(self, batch, table):\n"
           "    return fanout_mask_range(batch, *table)\n")
    # both the import and the call site are flagged
    assert ids(src, ANY) == ["KB127", "KB127"]


def test_kb127_flags_attribute_reference_and_wmajor():
    src = ("from kubebrain_tpu.ops import fanout\n"
           "def f(self, ek, tbl):\n"
           "    return fanout.fanout_mask_range_wmajor(ek, *tbl)\n")
    assert ids(src, "kubebrain_tpu/fanout/matcher.py") == ["KB127"]


def test_kb127_allows_the_dispatch_funnels():
    src = ("from ..ops.fanout import fanout_mask_range_wmajor\n"
           "def local(ek, ws):\n"
           "    return fanout_mask_range_wmajor(ek, ws)\n")
    assert ids(src, "kubebrain_tpu/fanout/dispatch.py") == []
    assert ids(src, "kubebrain_tpu/ops/fanout.py") == []
    assert ids(src, "kubebrain_tpu/parallel/step.py") == []
    # and code outside kubebrain_tpu (tests, tools) is out of scope
    assert ids(src, "tests/test_fanout_device.py") == []


def test_kb127_quiet_on_mask_consumers():
    src = ("def stream(self, batch, specs, version):\n"
           "    mask = self._fanout_matcher(batch, specs, version=version)\n"
           "    return mask.any(axis=0)\n")
    assert ids(src, ANY) == []


# ------------------------------------------------------------ registry/CLI
def test_registry_has_all_rules():
    assert set(RULES) == {"KB101", "KB102", "KB103", "KB104", "KB105", "KB106",
                          "KB107", "KB108", "KB109", "KB110", "KB111",
                          "KB116", "KB117", "KB118", "KB127"}
    for rule in RULES.values():
        assert rule.summary


def test_syntax_error_reported_not_raised():
    assert ids("def f(:\n", ANY) == ["KB000"]


def test_cli_clean_on_this_repo():
    """The acceptance invariant: the shipped tree lints clean."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kblint", "kubebrain_tpu", "tools", "tests"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kblint", "--list-rules"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0
    for rid in ("KB101", "KB102", "KB103", "KB104", "KB105"):
        assert rid in proc.stdout


# ------------------------------------------------------------------- KB118
RETRY_PKG = "kubebrain_tpu/backend/x.py"


def test_kb118_flags_unbounded_while_true_retry():
    src = (
        "import time\n"
        "def f(op):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except Exception:\n"
        "            continue\n"
    )
    assert ids(src, RETRY_PKG) == ["KB118"]


def test_kb118_allows_bounded_retry_and_deadline():
    bounded = (
        "import time, random\n"
        "def f(op):\n"
        "    for attempt in range(5):\n"
        "        try:\n"
        "            return op()\n"
        "        except Exception:\n"
        "            time.sleep(0.1 * random.uniform(0.5, 1.5))\n"
    )
    assert ids(bounded, RETRY_PKG) == []
    deadline = (
        "import time, random\n"
        "def f(op):\n"
        "    deadline = time.monotonic() + 5\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except Exception:\n"
        "            if time.monotonic() > deadline:\n"
        "                raise\n"
    )
    assert ids(deadline, RETRY_PKG) == []


def test_kb118_flags_constant_sleep_without_jitter():
    src = (
        "import time\n"
        "def f(op):\n"
        "    attempts = 0\n"
        "    while attempts < 5:\n"
        "        try:\n"
        "            return op()\n"
        "        except Exception:\n"
        "            attempts += 1\n"
        "        time.sleep(0.25)\n"
    )
    assert ids(src, RETRY_PKG) == ["KB118"]
    jittered = src.replace("time.sleep(0.25)",
                           "time.sleep(0.25 * jitter())")
    assert ids(jittered, RETRY_PKG) == []


def test_kb118_flags_sleep_under_lock_in_retry_loop():
    src = (
        "import time, random\n"
        "def f(self, op):\n"
        "    for attempt in range(4):\n"
        "        with self._lock:\n"
        "            try:\n"
        "                return op()\n"
        "            except Exception:\n"
        "                pass\n"
        "            time.sleep(0.1 * random.uniform(0.5, 1.5))\n"
    )
    out = [f for f in lint_source(src, RETRY_PKG) if f.rule_id == "KB118"]
    assert [f.rule_id for f in out] == ["KB118"]
    assert "lock" in out[0].message


def test_kb118_error_captured_for_delivery_is_not_a_retry():
    # a dispatcher loop that binds the exception and hands it to the
    # waiting caller is delivering, not retrying (the scheduler's shape)
    src = (
        "def f(q):\n"
        "    while True:\n"
        "        req = q.get()\n"
        "        try:\n"
        "            result, err = req.fn(), None\n"
        "        except Exception as e:\n"
        "            result, err = None, e\n"
        "        req.finish(result, err)\n"
    )
    assert ids(src, RETRY_PKG) == []


def test_kb118_scoped_to_serving_packages_and_suppressible():
    src = (
        "def f(op):\n"
        "    while True:\n"
        "        try:\n"
        "            return op()\n"
        "        except Exception:\n"
        "            continue\n"
    )
    # tools/tests are out of scope
    assert ids(src, "tools/kblint/x.py") == []
    assert ids(src, "tests/x.py") == []
    assert ids(src, "kubebrain_tpu/workload/x.py") == []
    # faults/ and client.py are serving-path
    assert ids(src, "kubebrain_tpu/faults/x.py") == ["KB118"]
    assert ids(src, "kubebrain_tpu/client.py") == ["KB118"]
    sup = src.replace(
        "    while True:",
        "    while True:  # kblint: disable=KB118 -- test fixture")
    assert ids(sup, RETRY_PKG) == []


def test_kb110_covers_faults_package():
    # the fault schedule's replayability contract extends KB110 to faults/
    src = "import random\ndef lay():\n    return random.random()\n"
    assert ids(src, "kubebrain_tpu/faults/x.py") == ["KB110"]
    src2 = "import time\ndef lay():\n    return time.time()\n"
    assert ids(src2, "kubebrain_tpu/faults/x.py") == ["KB110"]
    seeded = ("import random\ndef lay(seed):\n"
              "    return random.Random(seed).random()\n")
    assert ids(seeded, "kubebrain_tpu/faults/x.py") == []
