"""chip_smoke.py, as far as a chipless box can hold it: it must FAIL here
(no quiet CPU run), its drive-and-compare functions must pass against a
correct server and fail on one wrong oracle row, and the compile cache must
live where the rule says."""

import json
import os
import subprocess
import sys
import time

import pytest

import chip_smoke
from kubebrain_tpu.cli import build_endpoint, build_parser
from kubebrain_tpu.util import jaxcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chipless_run_fails_fast_and_names_the_backend():
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert r.stdout.strip() == "", "a failed run prints no result"
    assert "Unable to initialize backend 'tpu'" in r.stderr
    assert "parent imported jax: False" in r.stderr


@pytest.fixture
def cpu_server():
    """The smoke's first server, in-process on the CPU: the same flags minus
    the chip (--use-pallas would run interpreted here; the kernels have
    their own differential tests)."""
    client_port, info_port = chip_smoke.free_port(), chip_smoke.free_port()
    args = build_parser().parse_args([
        "--single-node", "--storage", "tpu", "--inner-storage", "native",
        "--host", "127.0.0.1", "--client-port", str(client_port),
        "--peer-port", str(chip_smoke.free_port()),
        "--info-port", str(info_port), "--mesh-part", "1",
        "--sched-batch", "8", "--compact-interval", "86400", "--tpu-fanout",
    ])
    endpoint, backend, store = build_endpoint(args)
    endpoint.run()
    ctx = chip_smoke.Ctx(
        f"127.0.0.1:{client_port}", info_port, chip_smoke.Oracle(21),
        n_keys=2000, n_devices=1, device_prefix="TFRT_CPU", seed=21)
    yield ctx
    ctx.close()
    endpoint.close()
    backend.close()
    store.close()


def test_drive_and_compare_pass_on_a_correct_server(cpu_server):
    ctx = cpu_server
    chip_smoke.drive_first_server(ctx)
    chip_smoke.drive_restarted_server(ctx)
    assert ctx.obs["keys_loaded"] == 2000 and not ctx.obs["keys_cut"]
    assert ctx.obs["rows"] == 2000 + 2 * 200 + 100
    for name in ("concurrent", "concurrent_restarted"):
        formed = ctx.obs["query_batches"][name]
        assert formed["batches"] > 0 and formed["riders"] >= formed["batches"]
    assert ctx.obs["fanout_dispatches"] > 0
    assert ctx.obs["compact_victims"] > 0
    assert list(ctx.obs["mirror_bytes"]) == ["TFRT_CPU_0"]
    for phase in ("load", "concurrent", "churn_and_watch", "snapshot",
                  "paged", "compact", "fanout", "range_all_restarted",
                  "concurrent_restarted"):
        assert phase in ctx.checks


def test_one_wrong_oracle_row_fails_the_run(cpu_server):
    def corrupt(oracle):
        ver, rev = oracle.live[0]
        oracle.live[0] = (ver, rev + 1)

    with pytest.raises(chip_smoke.SmokeFailure, match="differs from the oracle"):
        chip_smoke.drive_first_server(cpu_server, tamper=corrupt)


def test_a_server_on_the_wrong_device_fails_the_run(cpu_server):
    """Right answers from the wrong place: the default prefix is the chip's."""
    cpu_server.device_prefix = "TPU"
    with pytest.raises(chip_smoke.SmokeFailure, match="expected a TPU"):
        chip_smoke.drive_first_server(cpu_server)


def test_last_stdout_line_is_exactly_the_verdict():
    """The chip check parses the last line and refuses any other key."""
    summary = {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               "checks": ["pallas:load"], "claim": None}
    lines = chip_smoke.result_lines(summary)
    assert json.loads(lines[0]) == summary
    assert json.loads(lines[-1]) == {"ok": True, "device": summary["device"]}
    assert all("\n" not in line for line in lines)


@pytest.fixture
def config_updates(monkeypatch):
    import jax

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_compile_cache_env_decides_when_set(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert jaxcache.use_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in config_updates
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"


def test_compile_cache_defaults_into_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jaxcache.use_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want
