"""repro.analysis rule pack: each rule must fire on a known-bad fixture
and stay quiet on the fixed version of the same code, suppressions must
downgrade findings without hiding them, and the repo's own hot-path
packages must be finding-free (the self-hosting gate that keeps the CI
lint lane meaningful)."""
import os

import pytest

from repro.analysis import all_rules, analyze_paths, analyze_source, run_cli

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src", "repro")


def _live(src, select=None):
    return [f for f in analyze_source(src, select=select) if not f.suppressed]


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# R001 host transfer inside jit
# ---------------------------------------------------------------------------

BAD_R001 = """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(x):
    y = np.asarray(x)
    return jnp.sum(jnp.asarray(y))
"""

BAD_R001_TRANSITIVE = """
import functools
import jax
import jax.numpy as jnp

def helper(x):
    return float(x[0]) + 1.0

@functools.partial(jax.jit, static_argnames=("n",))
def step(x, n):
    return helper(x) * n
"""

GOOD_R001 = """
import jax
import jax.numpy as jnp
import numpy as np

def host_prep(a):
    return np.asarray(a, np.int32)  # outside any jit root: fine

@jax.jit
def step(x):
    return jnp.sum(x * 2)
"""


def test_r001_fires_on_numpy_call_in_jit():
    findings = _live(BAD_R001, select=["R001"])
    assert _rules_of(findings) == {"R001"}
    assert any("np.asarray" in f.message for f in findings)


def test_r001_fires_through_the_call_graph():
    findings = _live(BAD_R001_TRANSITIVE, select=["R001"])
    assert _rules_of(findings) == {"R001"}
    assert all(f.line for f in findings)


def test_r001_quiet_on_host_side_numpy():
    assert _live(GOOD_R001, select=["R001"]) == []


BAD_R001_SHARD_MAP = """
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

def local(x):
    return np.asarray(x)

step = jax.jit(jax.shard_map(local, mesh=None, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False))
"""


def test_r001_fires_inside_jax_shard_map():
    findings = _live(BAD_R001_SHARD_MAP, select=["R001"])
    assert _rules_of(findings) == {"R001"}


def test_shard_map_bodies_of_distributed_are_jit_reachable():
    from repro.analysis.engine import _parse_context
    path = os.path.join(SRC, "core", "distributed.py")
    with open(path) as f:
        ctx, errors = _parse_context(f.read(), path)
    assert not errors
    assert {"local_step", "local_round", "local_dedupe",
            "local_decode"} <= ctx.jit_reachable


# ---------------------------------------------------------------------------
# R002 dtype-contract drift
# ---------------------------------------------------------------------------

BAD_R002_LITERAL = """
import numpy as np

def pack(w):
    w = np.uint64(w)
    return w + 3
"""

BAD_R002_NARROW = """
import numpy as np

def truncate(words):
    w = np.uint64(words)
    return w.astype(np.int32)
"""

BAD_R002_JNP64 = """
import jax.numpy as jnp

def keys(x):
    return x.astype(jnp.uint64)
"""

GOOD_R002 = """
import numpy as np

def pack(w):
    w = np.uint64(w)
    return w + np.uint64(3)

def low_bits(words):
    w = np.uint64(words)
    return (w & np.uint64(0xFFFF)).astype(np.int32)
"""


def test_r002_fires_on_u64_literal_mix():
    assert _rules_of(_live(BAD_R002_LITERAL, select=["R002"])) == {"R002"}


def test_r002_fires_on_narrowing_cast():
    assert _rules_of(_live(BAD_R002_NARROW, select=["R002"])) == {"R002"}


def test_r002_fires_on_jnp_64bit_dtype():
    # with x64 disabled jnp.uint64 silently produces 32-bit values
    assert _rules_of(_live(BAD_R002_JNP64, select=["R002"])) == {"R002"}


def test_r002_quiet_on_typed_constants_and_masked_narrowing():
    assert _live(GOOD_R002, select=["R002"]) == []


# ---------------------------------------------------------------------------
# R003 python control flow on traced values
# ---------------------------------------------------------------------------

BAD_R003 = """
import jax
import jax.numpy as jnp

@jax.jit
def relu_or_neg(x):
    if x.sum() > 0:
        return x
    return -x
"""

GOOD_R003 = """
import jax
import jax.numpy as jnp

@jax.jit
def relu_or_neg(x, *, flip: bool = False):
    if flip:  # static kwarg: fine
        x = -x
    return jnp.where(x > 0, x, -x)
"""


def test_r003_fires_on_traced_branch():
    assert _rules_of(_live(BAD_R003, select=["R003"])) == {"R003"}


def test_r003_quiet_on_static_branch_and_where():
    assert _live(GOOD_R003, select=["R003"]) == []


# ---------------------------------------------------------------------------
# R004 unsynced benchmark timing
# ---------------------------------------------------------------------------

BAD_R004 = """
import time
import jax

def bench(fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    dt = time.perf_counter() - t0
    return out, dt
"""

GOOD_R004 = """
import time
import jax

def bench(fn, x):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    dt = time.perf_counter() - t0
    return out, dt
"""


def test_r004_fires_on_unsynced_window():
    assert _rules_of(_live(BAD_R004, select=["R004"])) == {"R004"}


def test_r004_quiet_when_blocked_until_ready():
    assert _live(GOOD_R004, select=["R004"]) == []


# ---------------------------------------------------------------------------
# R005 jit-cache hazards
# ---------------------------------------------------------------------------

BAD_R005_LOOP = """
import jax

def run(xs):
    outs = []
    for x in xs:
        f = jax.jit(lambda v: v * 2)
        outs.append(f(x))
    return outs
"""

BAD_R005_FACTORY = """
import jax

def make_step(scale):
    return jax.jit(lambda v: v * scale)
"""

GOOD_R005 = """
import functools
import jax

@functools.lru_cache(maxsize=8)
def make_step(scale):
    return jax.jit(lambda v: v * scale)

step = jax.jit(lambda v: v * 2)  # module-level: compiled once
"""


def test_r005_fires_on_jit_in_loop():
    assert _rules_of(_live(BAD_R005_LOOP, select=["R005"])) == {"R005"}


def test_r005_fires_on_uncached_factory():
    assert _rules_of(_live(BAD_R005_FACTORY, select=["R005"])) == {"R005"}


def test_r005_quiet_on_cached_factory_and_module_jit():
    assert _live(GOOD_R005, select=["R005"]) == []


# ---------------------------------------------------------------------------
# engine mechanics: suppressions, syntax errors, CLI exit codes
# ---------------------------------------------------------------------------

SUPPRESSED = """
import jax
import numpy as np

@jax.jit
def step(x):
    return np.asarray(x)  # repro: noqa[R001] parity check reads back on host
"""

SUPPRESSED_OTHER_RULE = """
import jax
import numpy as np

@jax.jit
def step(x):
    return np.asarray(x)  # repro: noqa[R004]
"""


def test_noqa_downgrades_but_keeps_the_finding():
    findings = analyze_source(SUPPRESSED, select=["R001"])
    assert len(findings) == 1
    assert findings[0].suppressed


def test_noqa_for_a_different_rule_does_not_apply():
    findings = analyze_source(SUPPRESSED_OTHER_RULE, select=["R001"])
    assert [f.suppressed for f in findings] == [False]


def test_bare_noqa_suppresses_every_rule():
    src = SUPPRESSED.replace("noqa[R001] parity check reads back on host",
                             "noqa")
    assert all(f.suppressed for f in analyze_source(src))


def test_syntax_error_becomes_e999():
    findings = analyze_source("def f(:\n    pass\n")
    assert [f.rule for f in findings] == ["E999"]


def test_rule_pack_is_complete():
    assert set(all_rules()) == {
        "R001", "R002", "R003", "R004", "R005",
        "R006", "R007", "R008", "R009",
    }


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_R001)
    good = tmp_path / "good.py"
    good.write_text(GOOD_R001)
    assert run_cli([str(good), "--no-cache"]) == 0
    assert run_cli([str(bad), "--no-cache"]) == 1
    out = capsys.readouterr()
    assert "R001" in out.out
    assert run_cli([str(bad), "--no-cache", "--select", "R004"]) == 0


# ---------------------------------------------------------------------------
# R006 collective contracts (mesh-axis universe + all_to_all divisibility)
# ---------------------------------------------------------------------------

BAD_R006_AXIS = """
import jax

mesh = jax.make_mesh((4,), ("data",))

def local_sum(x):
    return jax.lax.psum(x, "model")
"""

BAD_R006_SPLIT = """
import jax

mesh = jax.make_mesh((4,), ("data",))

def exchange(x):
    y = x.reshape(6, 128)
    return jax.lax.all_to_all(y, "data", 0, 0)
"""

GOOD_R006 = """
import jax
from jax import lax

mesh = jax.make_mesh((4,), ("data",))

def local_sum(x, axis_name="data"):
    return lax.psum(x, axis_name)

def exchange(x):
    y = x.reshape(8, 128)
    return lax.all_to_all(y, "data", 0, 0)

def shards(m):
    return m.shape["data"]
"""


def test_r006_fires_on_undeclared_axis():
    findings = _live(BAD_R006_AXIS, select=["R006"])
    assert _rules_of(findings) == {"R006"}
    assert any("model" in f.message for f in findings)


def test_r006_fires_on_indivisible_all_to_all_split():
    findings = _live(BAD_R006_SPLIT, select=["R006"])
    assert _rules_of(findings) == {"R006"}
    assert any("divisible" in f.message for f in findings)


def test_r006_fires_on_undeclared_mesh_shape_key():
    src = GOOD_R006.replace('m.shape["data"]', 'm.shape["expert"]')
    findings = _live(src, select=["R006"])
    assert _rules_of(findings) == {"R006"}


def test_r006_quiet_on_declared_axes_and_dividing_split():
    assert _live(GOOD_R006, select=["R006"]) == []


def test_r006_quiet_without_any_mesh_declaration():
    # no universe to check against: stay silent rather than guess
    src = "import jax\n\ndef f(x):\n    return jax.lax.psum(x, 'model')\n"
    assert _live(src, select=["R006"]) == []


def test_r006_resolves_conditional_mesh_construction():
    # axes bound through a local name with branch-dependent literals
    # (the launch/mesh.py idiom) still populate the universe
    src = """
import jax

def make(multi: bool = False):
    shape = (2, 4) if multi else (4,)
    axes = ("pod", "data") if multi else ("data",)
    return jax.make_mesh(shape, axes)

def f(x):
    return jax.lax.psum(x, "pod")

def g(x):
    return jax.lax.psum(x, "model")
"""
    findings = _live(src, select=["R006"])
    assert len(findings) == 1 and "model" in findings[0].message


# ---------------------------------------------------------------------------
# R007 padding / sentinel contracts
# ---------------------------------------------------------------------------

BAD_R007_PAD = """
import numpy as np

def mean_rows(x, n_real: int):
    padded = np.pad(x, ((0, 8), (0, 0)))
    return np.mean(padded)
"""

BAD_R007_SENTINEL = """
import numpy as np

def decode(keys):
    words = np.full((4, 16), np.uint32(0xFFFFFFFF))
    words[: len(keys)] = keys
    return unpack_words_host(words)
"""

GOOD_R007 = """
import numpy as np

def mean_rows(x, n_real: int):
    padded = np.pad(x, ((0, 8), (0, 0)))
    return np.mean(padded[:n_real])

def decode(keys, words):
    live = words[words != np.uint32(0xFFFFFFFF)]
    return unpack_words_host(live)
"""


def test_r007_fires_on_reduction_over_padded():
    findings = _live(BAD_R007_PAD, select=["R007"])
    assert _rules_of(findings) == {"R007"}
    assert any("mean" in f.message for f in findings)


def test_r007_fires_on_unfiltered_sentinel_unpack():
    findings = _live(BAD_R007_SENTINEL, select=["R007"])
    assert _rules_of(findings) == {"R007"}
    assert any("sentinel" in f.message for f in findings)


def test_r007_quiet_on_sliced_and_filtered_uses():
    assert _live(GOOD_R007, select=["R007"]) == []


def test_r007_taint_does_not_cross_arbitrary_calls():
    # a callee may consume the padding internally (kernel launches whose
    # outputs are per-lane ranks): its results are not padded values
    src = """
import numpy as np

def histogram(x):
    padded = np.pad(x, (0, 8))
    counts = launch_kernel(padded)
    return np.cumsum(counts)
"""
    assert _live(src, select=["R007"]) == []


# ---------------------------------------------------------------------------
# R008 serving concurrency
# ---------------------------------------------------------------------------

BAD_R008_BLOCKING = """
import time

class Lane:
    def drain(self):
        with self._lock:
            time.sleep(0.01)
            self.flushed += 1
"""

BAD_R008_UNGUARDED = """
class Metrics:
    def __init__(self):
        self.served = 0

    def record(self):
        with self._lock:
            self.served += 1

    def record_fast(self):
        self.served += 1
"""

GOOD_R008 = """
import time

class Lane:
    def __init__(self):
        self.flushed = 0

    def drain(self):
        batch = self.q.get()
        with self._lock:
            self.flushed += 1
        time.sleep(0.01)

    def report(self):
        with self._lock:
            self.flushed += 1
"""


def test_r008_fires_on_blocking_call_under_lock():
    findings = _live(BAD_R008_BLOCKING, select=["R008"])
    assert _rules_of(findings) == {"R008"}
    assert any("blocking" in f.message for f in findings)


def test_r008_fires_on_inconsistently_guarded_attribute():
    findings = _live(BAD_R008_UNGUARDED, select=["R008"])
    assert _rules_of(findings) == {"R008"}
    assert any("record_fast" in f.message for f in findings)


def test_r008_quiet_on_consistent_locking():
    # __init__ writes and lock-free single-lane classes are fine
    assert _live(GOOD_R008, select=["R008"]) == []


# ---------------------------------------------------------------------------
# R009 pallas kernel shapes
# ---------------------------------------------------------------------------

BAD_R009_GRID = """
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def launch(x):
    return pl.pallas_call(kernel, grid=(x.shape[0] // 8,))(x)
"""

BAD_R009_OOB = """
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    o_ref[0, 0] = x_ref[2, 0]

def launch(x):
    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((1, 128), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((1, 128), lambda r: (r, 0)),
    )(x)
"""

GOOD_R009 = """
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    o_ref[0, 127] = x_ref[0, 0]

def launch(x):
    rows = x.shape[0]
    assert rows % 8 == 0
    spec = pl.BlockSpec((1, 128), lambda r: (r, 0))
    return pl.pallas_call(
        kernel, grid=(rows // 8,), in_specs=[spec], out_specs=spec,
    )(x)
"""


def test_r009_fires_on_unguarded_grid_floordiv():
    findings = _live(BAD_R009_GRID, select=["R009"])
    assert _rules_of(findings) == {"R009"}
    assert any("divisibility" in f.message for f in findings)


def test_r009_fires_on_out_of_bounds_static_ref_index():
    findings = _live(BAD_R009_OOB, select=["R009"])
    assert _rules_of(findings) == {"R009"}
    assert any("exceeds" in f.message for f in findings)


def test_r009_quiet_on_guarded_grid_and_in_bounds_indices():
    # the divisibility assert covers the grid; index 127 < block 128,
    # and the spec resolves through its local name binding
    assert _live(GOOD_R009, select=["R009"]) == []


# ---------------------------------------------------------------------------
# noqa spans: first-line suppression of multi-line statements
# ---------------------------------------------------------------------------


def test_noqa_on_first_line_covers_the_whole_statement():
    src = """
import jax
import numpy as np

@jax.jit
def step(x):
    y = (  # repro: noqa[R001]
        np.asarray(x))
    return y
"""
    findings = analyze_source(src, select=["R001"])
    assert len(findings) == 1
    assert findings[0].suppressed


def test_noqa_on_compound_header_does_not_blanket_the_body():
    src = """
import jax
import numpy as np

@jax.jit
def step(  # repro: noqa[R001]
    x,
):
    return np.asarray(x)
"""
    findings = analyze_source(src, select=["R001"])
    assert [f.suppressed for f in findings] == [False]


# ---------------------------------------------------------------------------
# cross-module reachability (phase-1 index)
# ---------------------------------------------------------------------------


def _write_pkg(tmp_path, a_src, b_src):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text(a_src)
    (pkg / "b.py").write_text(b_src)
    return pkg


XMOD_HELPER = """
import numpy as np

def helper(x):
    return np.asarray(x)
"""

XMOD_JIT_CALLER = """
import jax
from .b import helper

@jax.jit
def step(x):
    return helper(x)
"""

XMOD_HOST_CALLER = """
from .b import helper

def prep(x):
    return helper(x)
"""


def test_cross_module_jit_reachability_flags_the_helper(tmp_path):
    pkg = _write_pkg(tmp_path, XMOD_JIT_CALLER, XMOD_HELPER)
    findings = [f for f in analyze_paths([str(pkg)], select=["R001"])
                if not f.suppressed]
    assert _rules_of(findings) == {"R001"}
    assert all(f.path.endswith("b.py") for f in findings)


def test_cross_module_reachability_quiet_for_host_only_callers(tmp_path):
    pkg = _write_pkg(tmp_path, XMOD_HOST_CALLER, XMOD_HELPER)
    findings = [f for f in analyze_paths([str(pkg)], select=["R001"])
                if not f.suppressed]
    assert findings == []


def test_cross_module_reachability_through_package_reexport(tmp_path):
    pkg = _write_pkg(tmp_path, XMOD_JIT_CALLER.replace(
        "from .b import helper", "from . import helper"), XMOD_HELPER)
    (pkg / "__init__.py").write_text("from .b import helper\n")
    findings = [f for f in analyze_paths([str(pkg)], select=["R001"])
                if not f.suppressed]
    assert _rules_of(findings) == {"R001"}


# ---------------------------------------------------------------------------
# on-disk findings cache
# ---------------------------------------------------------------------------


def test_cache_round_trip_returns_identical_findings(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(BAD_R001)
    cache = tmp_path / "cache.json"
    first = analyze_paths([str(mod)], cache_path=str(cache))
    assert cache.exists()
    second = analyze_paths([str(mod)], cache_path=str(cache))
    assert second == first
    assert _rules_of(second) == {"R001"}


def test_cache_hits_skip_the_rule_run(tmp_path):
    import json

    mod = tmp_path / "mod.py"
    mod.write_text(BAD_R001)
    cache = tmp_path / "cache.json"
    analyze_paths([str(mod)], cache_path=str(cache))
    # poison the cached findings in place (same digest/mtime/size): a
    # true cache hit must surface the poisoned copy, not re-run rules
    raw = json.loads(cache.read_text())
    (entry,) = raw["files"].values()
    entry["findings"][0]["message"] = "poisoned-cache-entry"
    cache.write_text(json.dumps(raw))
    got = analyze_paths([str(mod)], cache_path=str(cache))
    assert [f.message for f in got] == ["poisoned-cache-entry"]


def test_cache_invalidates_on_file_edit(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(BAD_R001)
    cache = tmp_path / "cache.json"
    assert _rules_of(analyze_paths([str(mod)], cache_path=str(cache))) \
        == {"R001"}
    mod.write_text(GOOD_R001)
    assert analyze_paths([str(mod)], cache_path=str(cache)) == []


def test_cache_invalidates_when_a_dependency_changes_reachability(tmp_path):
    # b.py never changes; editing ONLY a.py makes b.helper jit-reachable,
    # so the cache must re-check b.py (the digest carries injected
    # cross-module facts, not just the file's own mtime/size)
    pkg = _write_pkg(tmp_path, XMOD_HOST_CALLER, XMOD_HELPER)
    cache = tmp_path / "cache.json"
    quiet = [f for f in analyze_paths([str(pkg)], select=["R001"],
                                      cache_path=str(cache))
             if not f.suppressed]
    assert quiet == []
    (pkg / "a.py").write_text(XMOD_JIT_CALLER)
    loud = [f for f in analyze_paths([str(pkg)], select=["R001"],
                                     cache_path=str(cache))
            if not f.suppressed]
    assert _rules_of(loud) == {"R001"}
    assert all(f.path.endswith("b.py") for f in loud)


# ---------------------------------------------------------------------------
# CLI output formats
# ---------------------------------------------------------------------------


def test_cli_github_format_emits_annotations(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_R001)
    assert run_cli([str(bad), "--no-cache", "--format", "github"]) == 1
    out = capsys.readouterr().out
    assert "::error file=" in out
    assert "R001" in out


def test_cli_warn_only_reports_but_exits_zero(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_R001)
    assert run_cli([str(bad), "--no-cache", "--warn-only",
                    "--format", "github"]) == 0
    out = capsys.readouterr().out
    assert "::warning file=" in out


def test_cli_writes_json_report(tmp_path):
    import json

    bad = tmp_path / "bad.py"
    bad.write_text(BAD_R001)
    report = tmp_path / "report.json"
    assert run_cli([str(bad), "--no-cache", "--report", str(report)]) == 1
    data = json.loads(report.read_text())
    assert any(f["rule"] == "R001" for f in data)


# ---------------------------------------------------------------------------
# self-hosting gate: the repo's own hot-path packages stay finding-free
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pkg", ["core", "kernels", "streaming", "serving"])
def test_self_hosting_hot_paths_are_clean(pkg):
    findings = analyze_paths([os.path.join(SRC, pkg)])
    live = [f.format() for f in findings if not f.suppressed]
    assert live == [], "\n".join(live)
