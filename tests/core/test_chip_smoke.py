"""Bring-up contract (ISSUE 21), rehearsed on the CPU before chip time is
spent: ``chip_smoke.py``'s leg bodies at a tiny size, its refusal to run
off a TPU, where the compile cache goes, and the fallbacks that used to
hide the device now failing loudly."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.core, pytest.mark.distributed]

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SMOKE = os.path.join(REPO, "chip_smoke.py")

spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
chip_smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(chip_smoke)

# the smoke's own structure at a size the CPU runs in seconds; bf16 like
# the real thing (the host engine's bf16 cotangent bug hid behind fp32)
TINY = {
    "flash_mha": (2, 128, 2, 2, 16),
    "flash_gqa": (1, 128, 4, 2, 16),
    "flash_gqa_h128": (1, 128, 4, 1, 32),
    "ce": (64, 512),
    "model": ["model.hidden_size=32", "model.num_hidden_layers=2",
              "model.num_attention_heads=2", "model.vocab_size=64",
              "model.seq_length=8", "model.max_position_embeddings=16",
              "model.make_vocab_size_divisible_by=1"],
    "iters": 2,
}


def test_smoke_import_touches_neither_jax_nor_the_package():
    """The parent must never hold the chip its children need."""
    code = ("import sys, importlib.util as u; "
            f"s = u.spec_from_file_location('chip_smoke', {SMOKE!r}); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "print([n for n in sys.modules if n == 'jax' "
            "or n.startswith('hetu_galvatron_tpu')])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout
    assert out.strip() == "[]"


def test_script_entry_refuses_a_cpu_within_seconds():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, SMOKE], capture_output=True,
                          text=True, timeout=60, env=env)
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    # no result line: nothing on stdout parses as the final report
    for line in proc.stdout.splitlines():
        assert not line.startswith("{")


def test_kernel_leg_body_tiny_interpret():
    rep = chip_smoke.leg_kernels(TINY, interpret=True)
    assert set(rep) == {"flash_mha", "flash_gqa", "flash_gqa_h128",
                        "fused_ce", "device_memory"}
    assert set(rep["flash_mha"]["max_err"]) == {"out", "dq", "dk", "dv"}
    json.dumps(rep)  # what the child prints


def test_kernel_leg_fails_on_a_wrong_answer(monkeypatch):
    """A parity miss is a failure, not a number in a report."""
    monkeypatch.setitem(chip_smoke.TOL, "float32", 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="fused_ce"):
        chip_smoke._ce_parity(TINY["ce"], interpret=True)


@pytest.mark.parametrize("leg", sorted(chip_smoke.PLANS))
def test_train_leg_body_tiny(leg):
    rep = chip_smoke.leg_train(leg, TINY, expect_mosaic=False)
    assert len(rep["losses"]) == TINY["iters"]
    assert rep["attention_cores"] == ["xla"]
    assert rep["mosaic_custom_calls"] == 0
    assert rep["compile_cache"]["dir"]
    json.dumps(rep)


def test_train_leg_fails_when_the_xla_core_ran():
    """On the chip the trainer must report the flash core and Mosaic calls
    in the compiled step; a CPU run judged by the chip's rule fails."""
    with pytest.raises(chip_smoke.SmokeFailure, match="attention cores"):
        chip_smoke.leg_train("train1", TINY, expect_mosaic=True)


def test_spread_check():
    mem = [{"peak_bytes_in_use": b} for b in (100, 100, 100, 100)]
    chip_smoke.check_spread("leg", mem, 4)
    mem[0]["peak_bytes_in_use"] = 300  # chip 0 carries the model
    with pytest.raises(chip_smoke.SmokeFailure, match="device 0"):
        chip_smoke.check_spread("leg", mem, 4)
    mem[0]["peak_bytes_in_use"] = None  # a chip that holds nothing
    with pytest.raises(chip_smoke.SmokeFailure, match="no bytes"):
        chip_smoke.check_spread("leg", mem, 4)


def _canned_leg(leg, count, *, hits=0, losses=(10.99, 10.98)):
    return {"leg": leg, "ok": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": count},
            "versions": {"jax": "0.9.0"}, "losses": list(losses),
            "compile_cache": {"dir": "/c", "hits": hits, "writes": 2},
            "smoke_timings_s": {"backend_compile": 1.0}}


def test_parent_flow_with_canned_children(monkeypatch):
    """The parent's own decisions, with the children stubbed: which legs a
    host gets, the cache-hit requirement, the cross-plan loss check."""
    calls = []

    def fake_spawn(count, warm_hits=2, stray=0.0):
        def spawn(leg, deadline):
            calls.append(leg)
            hits = warm_hits if calls.count("train1") == 2 else 0
            losses = (10.99 + stray, 10.98) if leg.startswith("train4") \
                else (10.99, 10.98)
            return _canned_leg(leg, count, hits=hits, losses=losses)
        return spawn

    monkeypatch.setattr(chip_smoke, "spawn_leg", fake_spawn(1))
    rep = chip_smoke.run_parent()
    assert calls == ["kernels", "train1", "train1"]
    assert rep["ok"] and rep["device"]["count"] == 1
    assert set(rep["legs"]) == {"kernels", "train1", "train1_cached"}
    assert rep["versions"] == {"jax": "0.9.0"}
    json.dumps(rep)

    calls.clear()
    monkeypatch.setattr(chip_smoke, "spawn_leg", fake_spawn(4))
    rep = chip_smoke.run_parent()
    assert calls[3:] == ["train4_tp2dp2", "train4_pp2tp2"]

    calls.clear()  # a second process that hit nothing in the cache
    monkeypatch.setattr(chip_smoke, "spawn_leg", fake_spawn(1, warm_hits=0))
    with pytest.raises(chip_smoke.SmokeFailure, match="hit nothing"):
        chip_smoke.run_parent()

    calls.clear()  # a four-chip plan that trains something else
    monkeypatch.setattr(chip_smoke, "spawn_leg", fake_spawn(4, stray=0.5))
    with pytest.raises(chip_smoke.SmokeFailure, match="stray"):
        chip_smoke.run_parent()


def test_last_stdout_line_is_ok_and_device_and_nothing_else(
        monkeypatch, capsys):
    """The driver reads the last line: exactly ``ok`` and ``device``, the
    device exactly platform, kind and count. Everything else the smoke
    learned goes on the ``report:`` line before it."""
    monkeypatch.setattr(
        chip_smoke, "spawn_leg",
        lambda leg, deadline: _canned_leg(leg, 1, hits=2))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.splitlines()
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert isinstance(last["device"]["count"], int)
    assert lines[-2].startswith("report: ")
    assert set(json.loads(lines[-2][len("report: "):])) == {
        "ok", "device", "versions", "legs"}


def test_script_alone_fails_before_it_starts_a_child(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo: non-zero, no result, whatever devices the host shows."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    proc = subprocess.run([sys.executable, str(alone)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "is missing" in proc.stderr
    assert proc.stdout == ""


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

_CACHE_PROBE = (
    "import jax; "
    "from hetu_galvatron_tpu.cli.compile_cache import "
    "configure_compile_cache as c; "
    "print(c()); print(jax.config.jax_compilation_cache_dir)")


def _cache_probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         env=env, check=True).stdout.split()
    return out  # [returned dir, what jax itself holds]


def test_compile_cache_env_var_wins_and_nothing_is_set_in_code(tmp_path):
    want = str(tmp_path / "placed_from_outside")
    returned, jax_holds = _cache_probe(want)
    # jax read the variable itself; had the code set a path, it would
    # show here instead
    assert returned == want and jax_holds == want


def test_compile_cache_default_is_one_fixed_in_checkout_path():
    a = _cache_probe(None)
    b = _cache_probe(None)  # a second process: same path, or never a hit
    assert a == b
    assert a[0] == a[1] == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# fallbacks that hid the device
# ---------------------------------------------------------------------------


def test_num_devices_above_visible_raises():
    from hetu_galvatron_tpu.core.args_schema import CoreArgs
    from hetu_galvatron_tpu.runtime.initialize import (
        initialize,
        visible_world_size,
    )

    args = CoreArgs()
    args.parallel.num_devices = 4096
    with pytest.raises(ValueError, match="num_devices=4096"):
        initialize(args)
    with pytest.raises(ValueError, match="num_devices=4096"):
        visible_world_size(args)
    args.parallel.num_devices = 2  # asking for fewer is a choice
    assert visible_world_size(args) == 2


def test_kernel_entry_points_take_interpret_only_from_the_caller():
    """No inference from the backend: on a CPU the bare entry points ask
    for Mosaic and are refused; ``interpret=True`` is the caller's word."""
    import jax
    import jax.numpy as jnp

    from hetu_galvatron_tpu.ops.pallas.cross_entropy import fused_ce_nll
    from hetu_galvatron_tpu.ops.pallas.flash_attention import (
        flash_sdpa,
        make_flash_sdpa,
    )

    logits = jnp.zeros((64, 512), jnp.float32)
    labels = jnp.zeros((64,), jnp.int32)
    with pytest.raises(ValueError, match="interpret mode"):
        fused_ce_nll(logits, labels)
    assert fused_ce_nll(logits, labels, interpret=True).shape == (64,)

    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_sdpa(q, q, q)
    assert flash_sdpa(q, q, q, interpret=True).shape == q.shape

    # the mesh wrapper has no XLA core behind it any more: an untileable
    # length runs the kernel as one block instead of changing cores
    mesh = jax.sharding.Mesh(jax.devices()[:1], ("d0",))
    odd = jnp.zeros((1, 100, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        make_flash_sdpa(mesh)(odd, odd, odd)
    out = make_flash_sdpa(mesh, interpret=True)(odd, odd, odd)
    assert out.shape == odd.shape


def test_flash_kernel_rule_reads_every_device():
    from types import SimpleNamespace as D

    from hetu_galvatron_tpu.runtime.mesh import flash_kernel_runs

    tpu, cpu = D(platform="tpu"), D(platform="cpu")
    assert flash_kernel_runs(True, [tpu, tpu])
    assert not flash_kernel_runs(False, [tpu, tpu])
    assert not flash_kernel_runs(True, [cpu])
    with pytest.raises(ValueError, match="platforms"):
        flash_kernel_runs(True, [tpu, cpu])  # the first device is no answer
