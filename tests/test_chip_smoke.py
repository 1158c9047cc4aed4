"""chip_smoke.py must not rot between chip runs, and the Pallas agg kernel
gets its first tests: the script's query loop on the CPU mesh with the
device-only assertions off, the kernel in TPU interpret mode against the
XLA formulation, its cross-lowering for the chip, and the two process-level
rules the chip depends on (import takes no backend; the compile cache goes
where JAX_COMPILATION_CACHE_DIR says)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from blaze_tpu.config import conf
from blaze_tpu.ops import mxu_agg
from blaze_tpu.spark.validator import generate_tables

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_query_loop_q2_on_cpu(tmp_path):
    saved = conf.trace_enabled
    conf.trace_enabled = True  # whole_stage_fallback is a trace event
    try:
        paths, frames = generate_tables(str(tmp_path), rows=3000)
        (cell,) = chip_smoke.run_cells(
            paths, frames, [("q2_q06_core_agg", "bhj", 4)],
            device_checks=False)
    finally:
        conf.trace_enabled = saved
    assert len(cell["run_s"]) == 2
    assert cell["counters"]["stage_compiled"] >= 1
    assert cell["counters"]["mesh_devices"] == 4


def test_query_loop_refuses_a_fallback(tmp_path, monkeypatch):
    """A run that only passed through the resilience ladder fails the
    loop even though its answer is oracle-equal."""
    from blaze_tpu.spark import local_runner

    real = local_runner.run_plan

    def degraded(*a, run_info, **kw):
        out = real(*a, run_info=run_info, **kw)
        run_info["ladder_rung"] = 3
        run_info["task_fallbacks"] = 1
        return out

    monkeypatch.setattr(local_runner, "run_plan", degraded)
    paths, frames = generate_tables(str(tmp_path), rows=1500)
    with pytest.raises(RuntimeError, match="served by a fallback"):
        chip_smoke.run_cells(paths, frames,
                             [("q1_scan_filter_project", "bhj", 0)],
                             device_checks=False, runs=1)


def test_query_loop_cuts_repeat_runs_past_the_cutoff(tmp_path):
    paths, frames = generate_tables(str(tmp_path), rows=1500)
    (cell,) = chip_smoke.run_cells(
        paths, frames, [("q1_scan_filter_project", "bhj", 0)],
        device_checks=False, second_run_cutoff=0.0)
    assert len(cell["run_s"]) == 1 and "time limit" in cell["cut"]


def _kernel_inputs(n, groups, seed=3):
    rs = np.random.default_rng(seed)
    keys = jnp.asarray(rs.integers(0, groups, n).astype(np.int32))
    ok = jnp.asarray(rs.random(n) < 0.9)
    words = [jnp.asarray(rs.integers(-(1 << 31), 1 << 31, n)
                         .astype(np.int32)) for _ in range(2)]
    return keys, ok, words + [ok.astype(jnp.int32)]


def test_pallas_kernel_interpret_equals_xla():
    from jax.experimental.pallas import tpu as pltpu

    n, groups = 4096, 512
    gh = groups // mxu_agg._GL
    keys, ok, words = _kernel_inputs(n, groups)
    recipe = (("digit", 0, 0), ("digit", 0, 24), ("digit", 1, 8),
              ("raw", 2, 0))
    with pltpu.force_tpu_interpret_mode():
        got = mxu_agg._pallas_accumulate(keys, ok.astype(jnp.int32), words,
                                         recipe, gh)
    planes = mxu_agg._expand_words(words, recipe)
    want = mxu_agg._xla_accumulate(
        keys, ok, jnp.where(ok[:, None], planes, jnp.int8(0)), gh)
    assert got.shape == (gh, len(recipe) * mxu_agg._GL)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_kernel_cross_lowers_for_tpu_at_bench_shape():
    n, groups, planes = 1 << 21, 1 << 16, 7
    gh = groups // mxu_agg._GL
    assert mxu_agg._pick_tile(n, gh, planes * mxu_agg._GL) == 4096
    recipe = tuple(("digit", p % 2, 8 * (p % 4)) for p in range(planes))
    fn = jax.jit(lambda k, o, a, b: mxu_agg._pallas_accumulate(
        k, o, [a, b], recipe, gh))
    col = jax.ShapeDtypeStruct((n,), jnp.int32)
    text = fn.trace(col, col, col, col).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_corner_table_follows_pick_tile():
    """chip_smoke compiles these on the chip; off it, at least keep the
    table and the rule it probes from drifting apart."""
    for groups, planes, tile in chip_smoke.PALLAS_CORNERS:
        gh = groups // mxu_agg._GL
        assert mxu_agg._pick_tile(1 << 21, gh, planes * mxu_agg._GL) == tile
    # nothing past the scoped-vmem envelope is admitted
    assert mxu_agg._pick_tile(1 << 21, 512, 30 * mxu_agg._GL) is None
    assert mxu_agg._pick_tile(1 << 21, 256, 61 * mxu_agg._GL) is None


def test_last_line_is_the_drivers_contract():
    """The driver refuses any key beyond these in the last stdout line."""
    import json

    line = json.loads(chip_smoke.contract_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(line) == ["ok", "device"]


def _python(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    full["PYTHONPATH"] = REPO
    full.update(env)
    r = subprocess.run([sys.executable, "-c", code], env=full, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def test_import_initialises_no_backend():
    out = _python(
        "import blaze_tpu, blaze_tpu.runtime.memory, "
        "blaze_tpu.spark.local_runner\n"
        "from jax._src import xla_bridge\n"
        "print(xla_bridge.backends_are_initialized())",
        JAX_PLATFORMS="cpu")
    assert out == "False"


def test_compile_cache_is_placed_from_outside(tmp_path):
    show = ("import blaze_tpu, jax\n"
            "print(jax.config.jax_compilation_cache_dir)")
    given = str(tmp_path / "some" / "dir")
    assert _python(show, JAX_COMPILATION_CACHE_DIR=given) == given
    assert _python(show, JAX_COMPILATION_CACHE_DIR=given,
                   JAX_PLATFORMS="cpu") == given
    # unset: the checkout's .jax_cache — but not on the CPU test platform,
    # decided from the env string (neither child initialises a backend)
    assert _python(show) == os.path.join(REPO, ".jax_cache")
    assert _python(show, JAX_PLATFORMS="cpu") == "None"
