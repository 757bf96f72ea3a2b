"""chip_smoke.py and the device-tier plumbing it stands on (ISSUE 21): the
compile-cache rule, the content-keyed native library, a chip-wanting child
that refuses to run on a CPU, and the `auto` assertions having teeth."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from cometbft_tpu import native
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.sidecar import backend as be

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
TINY = [
    "--platform", "cpu", "--validators", "48", "--leaves", "64",
    "--window-commits", "3", "--window-validators", "16",
    "--devnet-blocks", "4", "--devnet-txs", "6",
]
# Tiny batches only reach the hybrid's device share with the split floor
# lowered and the planner's priors flattened (the knobs tests/test_hybrid.py
# uses).
HYBRID_TINY = {
    "CMTPU_HYBRID_MIN": "8", "CMTPU_DEV_RATE": "1000",
    "CMTPU_HOST_RATE": "1000", "CMTPU_DEV_OVERHEAD_MS": "0",
}


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("CMTPU_")}
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _cache_dir_of_fresh_process(env) -> str:
    out = subprocess.run(
        [sys.executable, "-c",
         "import cometbft_tpu.ops, jax; print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120, check=True,
    )
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_follows_env_else_checkout(tmp_path):
    """Importing the device tier leaves JAX_COMPILATION_CACHE_DIR alone
    when it is set and uses <checkout>/.jax_cache otherwise."""
    given = str(tmp_path / "given")
    assert _cache_dir_of_fresh_process(_env(JAX_COMPILATION_CACHE_DIR=given)) == given
    assert _cache_dir_of_fresh_process(_env()) == os.path.join(ROOT, ".jax_cache")


def test_native_library_name_tracks_source_bytes_and_flags(tmp_path, monkeypatch):
    for name in native._SOURCES:
        shutil.copy(os.path.join(native._HERE, name), tmp_path / name)
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    before = native._so_path()
    assert os.path.basename(before).startswith("libcmtpu_native-")
    assert native._so_path() == before
    with open(tmp_path / native._SOURCES[0], "ab") as f:
        f.write(b"\n")
    after = native._so_path()
    assert after != before
    monkeypatch.setattr(native, "_CFLAGS", (*native._CFLAGS, "-g"))
    assert native._so_path() not in (before, after)


def test_chip_wanting_child_refuses_a_cpu_before_any_compile(tmp_path):
    cache = tmp_path / "cache"
    out = subprocess.run(
        [sys.executable, SMOKE, "--child", "device"],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)), cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 3, out.stdout + out.stderr
    first = out.stdout.splitlines()[0]
    assert first.startswith("SMOKE ") and json.loads(first[6:])["platform"] == "cpu"
    assert '"step"' not in out.stdout
    assert not cache.exists() or not os.listdir(cache)


def test_smoke_fails_without_a_chip_and_prints_no_result(tmp_path):
    out = subprocess.run(
        [sys.executable, SMOKE, "--log-dir", str(tmp_path)],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def _signed(n):
    pvs = [ed25519.gen_priv_key_from_secret(b"smoke-teeth-%d" % i) for i in range(n)]
    msgs = [b"smoke-teeth-msg-%d" % i for i in range(n)]
    return (
        [pv.pub_key().bytes() for pv in pvs],
        msgs,
        [pv.sign(m) for pv, m in zip(pvs, msgs)],
    )


@pytest.mark.chaos
@pytest.mark.skipif(not native.available(), reason="native tier unavailable")
def test_auto_chain_assertions_have_teeth(monkeypatch):
    """The node phase's assertions pass on a healthy auto chain and fail
    once CMTPU_FAULTS=error:1 makes every hybrid call degrade to the cpu
    anchor — the answers stay correct either way, which is the point."""
    for k, v in HYBRID_TINY.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("CMTPU_BACKOFF_MS", "1")
    monkeypatch.setenv("CMTPU_BACKEND", "auto")  # open_auto_chain sets it too
    pubs, msgs, sigs = _signed(48)

    def run_chain():
        backend = chip_smoke.open_auto_chain("cpu")
        try:
            ok, bits = backend.batch_verify(pubs, msgs, sigs)
            assert ok and all(bits)
            return backend.counters()
        finally:
            backend.close()
            be.set_backend(None)

    healthy = run_chain()
    assert chip_smoke.check_auto_chain(healthy, "cpu")["device_lanes"] > 0
    monkeypatch.setenv("CMTPU_FAULTS", "error:1")
    faulty = run_chain()
    assert faulty["inner"]["degraded_calls"] > 0
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_auto_chain(faulty, "cpu")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_auto_chain(healthy, "tpu")


@pytest.mark.slow
def test_whole_smoke_tiny_on_cpu(tmp_path):
    out = subprocess.run(
        [sys.executable, SMOKE, *TINY, "--log-dir", str(tmp_path)],
        env=_env(**HYBRID_TINY), cwd=ROOT,
        capture_output=True, text=True, timeout=1500,
    )
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    *_, summary_line, result_line = out.stdout.splitlines()
    # The last line holds exactly these keys; the record is the line before.
    assert json.loads(result_line) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert summary_line.startswith("SUMMARY ")
    summary = json.loads(summary_line[len("SUMMARY "):])
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["phases"]["sidecar"]["lanes"]["device"] > 0
