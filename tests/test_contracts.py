"""The hot-path contract checker and the repo lint pass (repro.analysis).

Four layers of coverage:

* every registered contract case must pass on the real code (this is the
  tier-1 wiring of `python -m tools.lint --contracts`);
* a negative case for every contract CLAUSE: a minimal violating
  function/HLO the checker must flag, plus a compliant twin it must not;
* a negative case for every LINT RULE, same violating/compliant pairing,
  plus the pragma escape and jit-decorator recognition;
* mutation demonstrations: re-introducing the two bugs the contracts
  exist for — the iota-indexed frame gather (PR 5: an all-gather +
  all-reduce per scan iteration on the sharded pool) and the aliased
  ``init_telemetry`` buffers (PR 2: donation rejected at run time) — by
  actually compiling/executing the mutated variant and watching the
  checker fail.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import cases as caselib
from repro.analysis import contracts, hlo, lint
from repro.analysis.cases import BuiltCase

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the real hot paths pass their contracts ---------------------------------


@pytest.mark.parametrize("case", caselib.build_cases(),
                         ids=lambda c: c.name)
def test_hot_path_contract(case):
    report = contracts.check_case(case)
    assert report.ok, "\n".join(str(v) for v in report.violations)


def test_every_registered_contract_has_a_case():
    """A contract without a case is a pin that never fires."""
    covered = {c.contract for c in caselib.build_cases(include_sharded=False)}
    assert covered == set(contracts.registered_contracts())


# -- negative cases: one per contract clause ---------------------------------

# a minimal synthetic optimized-HLO module; the header carries a real
# alias map and the body a fusion whose inner ops must be counted too.
_CANNED_OK = textwrap.dedent("""\
    HloModule jit_f, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }
    %fused_computation (p0: f32[4,8]) -> f32[4,8] {
      %p0 = f32[4,8]{1,0} parameter(0)
      %transpose.1 = f32[8,4]{1,0} transpose(%p0), dimensions={1,0}
      ROOT %add.0 = f32[4,8]{1,0} add(%p0, %p0)
    }
    ENTRY %main (a: f32[4,8]) -> f32[4,8] {
      %a = f32[4,8]{1,0} parameter(0)
      ROOT %fusion = f32[4,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation
    }
""")


def _contract(**kw):
    kw.setdefault("name", "test_clause")
    return contracts.HotpathContract(**kw)


def test_clause_no_collectives_flags_and_twin_passes():
    bad = _CANNED_OK + "  %ar = f32[4,8]{1,0} all-reduce(%a), replica_groups={}\n"
    vs = contracts.check_hlo(_contract(), bad)
    assert [v.clause for v in vs] == ["no_collectives"]
    assert contracts.check_hlo(_contract(), _CANNED_OK) == []


def test_clause_no_host_transfers_flags_compiled_callback():
    """The violating twin is COMPILED, not canned: a host callback inside
    jit lowers to an xla_python_cpu_callback custom-call."""
    def bad(x):
        jax.debug.print("x0={v}", v=x[0])
        return x * 2.0

    def good(x):
        return x * 2.0

    x = jnp.ones((8,), jnp.float32)
    txt_bad = hlo.compiled_text(jax.jit(bad), x)
    txt_good = hlo.compiled_text(jax.jit(good), x)
    assert [v.clause for v in contracts.check_hlo(_contract(), txt_bad)] \
        == ["no_host_transfers"]
    assert contracts.check_hlo(_contract(), txt_good) == []


def test_clause_max_dtype_flags_f64():
    bad = _CANNED_OK + "  %c = f64[4,8]{1,0} convert(%a)\n"
    vs = contracts.check_hlo(_contract(), bad)
    assert [v.clause for v in vs] == ["max_dtype"]
    # widening the ceiling disables the clause:
    assert contracts.check_hlo(_contract(max_dtype="float64"), bad) == []


def test_clause_forbid_ops_sees_inside_fusions():
    """The canned module's transpose lives in a fusion body; the op
    histogram must count it anyway."""
    vs = contracts.check_hlo(_contract(forbid_ops=("transpose",)), _CANNED_OK)
    assert [v.clause for v in vs] == ["forbid_ops"]
    assert contracts.check_hlo(_contract(forbid_ops=("sort",)),
                               _CANNED_OK) == []


def test_clause_op_budget_flags_real_compiled_excess():
    def two_dus(buf, x):
        buf = jax.lax.dynamic_update_slice(buf, x, (0,))
        return jax.lax.dynamic_update_slice(buf, x, (4,))

    txt = hlo.compiled_text(jax.jit(two_dus), jnp.zeros((16,), jnp.float32),
                            jnp.ones((4,), jnp.float32))
    over = contracts.check_hlo(
        _contract(op_budget={"dynamic-update-slice": 1}), txt)
    assert [v.clause for v in over] == ["op_budget"]
    assert contracts.check_hlo(
        _contract(op_budget={"dynamic-update-slice": 2}), txt) == []


def test_clause_donation_static_flags_dropped_alias():
    """donate_argnums on an argument that cannot alias any output leaves
    no entry in the alias map; the static clause must notice."""
    import warnings

    def no_alias(x):
        return x.sum()                    # output shape != donated shape

    def aliases(x):
        return x + 1.0

    x = jnp.ones((128,), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # jax warns on unused donation
        txt_bad = hlo.compiled_text(jax.jit(no_alias, donate_argnums=0), x)
    txt_good = hlo.compiled_text(jax.jit(aliases, donate_argnums=0), x)
    bad = contracts.check_hlo(_contract(), txt_bad, donated_leaves=1)
    assert [v.clause for v in bad] == ["donation"]
    assert contracts.check_hlo(_contract(), txt_good, donated_leaves=1) == []


def test_clause_donation_runtime_flags_shared_buffer():
    """The aliased-buffer failure is invisible in the alias map (XLA
    still prints may-alias entries) and only fails in Execute(); the
    runtime probe must catch it — and must pass the un-aliased twin."""
    step = jax.jit(lambda pair: (pair[0] + 1.0, pair[1] * 2.0),
                   donate_argnums=0)

    z = jnp.zeros((64,), jnp.float32)
    bad = contracts.run_donation_probe(
        "test_clause", step, ((z, z),), {}, [(z, z)])
    assert [v.clause for v in bad] == ["donation"]
    assert "donate" in bad[0].message

    a, b = jnp.zeros((64,), jnp.float32), jnp.zeros((64,), jnp.float32)
    good = contracts.run_donation_probe(
        "test_clause", step, ((a, b),), {}, [(a, b)])
    assert good == []


def test_alias_count_parses_real_header():
    assert hlo.alias_count(_CANNED_OK) == 1
    assert hlo.alias_count("HloModule jit_f, is_scheduled=true") == 0
    many = ("HloModule m, input_output_alias={ "
            + ", ".join("{%d}: (%d, {}, may-alias)" % (i, i)
                        for i in range(13)) + " }, entry_layout={}")
    assert hlo.alias_count(many) == 13


# -- negative cases: one per lint rule ---------------------------------------


def _lint(src, path="src/repro/serving/fake.py"):
    return lint.lint_source(textwrap.dedent(src), path)


def test_rule_iota_gather_flags_and_twin_passes():
    bad = _lint("""
        import jax.numpy as jnp
        def gather(frames, cursor):
            return frames[jnp.arange(frames.shape[0]), cursor]
    """)
    assert [f.rule for f in bad] == ["iota-gather"]
    good = _lint("""
        import jax.numpy as jnp
        def gather(frames, cursor):
            idx = cursor[:, None, None]
            return jnp.take_along_axis(frames, idx, axis=1)[:, 0]
    """)
    assert good == []


def test_rule_iota_gather_ignores_at_updates():
    """`.at[arange(B), idx].add` is the scatter API, not the gather."""
    assert _lint("""
        import jax.numpy as jnp
        def scatter(buf, idx, vals):
            return buf.at[jnp.arange(buf.shape[0]), idx].add(vals)
    """, path="src/repro/kernels/fake.py") == []


def test_rule_eager_scatter_flags_and_twin_passes():
    bad = _lint("""
        def host_side(buf, x):
            return buf.at[0].set(x)
    """)
    assert [f.rule for f in bad] == ["eager-scatter"]
    # under jit (including functools.partial(jax.jit, ...)), allowed:
    assert _lint("""
        import functools, jax
        @functools.partial(jax.jit, donate_argnums=(0,))
        def device_side(buf, x):
            return buf.at[0].set(x)
    """) == []
    # outside serving/, out of scope for this rule:
    assert _lint("""
        def host_side(buf, x):
            return buf.at[0].set(x)
    """, path="src/repro/models/fake.py") == []


def test_rule_aliased_donation_flags_and_twin_passes():
    bad = _lint("""
        import jax.numpy as jnp
        def init(n):
            z = jnp.zeros((n,))
            return State(z, z, z)
    """)
    assert {f.rule for f in bad} == {"aliased-donation"}
    good = _lint("""
        import jax.numpy as jnp
        def init(n):
            def z():
                return jnp.zeros((n,))
            return State(z(), z(), z())
    """)
    assert good == []


def test_rule_blocking_in_driver_flags_and_twin_passes():
    path = "src/repro/serving/async_server.py"
    bad = _lint("""
        import numpy as np
        async def pump(out):
            val = np.asarray(out)
            ready = out.block_until_ready()
            x = float(out[0])
            return val, ready, x
    """, path)
    assert [f.rule for f in bad] == ["blocking-in-driver"] * 3
    good = _lint("""
        import numpy as np
        async def pump(loop, out):
            val = await loop.run_in_executor(None, _fetch, out)
            return val
        def _fetch(out):
            return np.asarray(out)   # sync helper, off the event loop
    """, path)
    assert good == []
    # same code outside the driver files is out of scope:
    assert _lint("""
        import numpy as np
        async def pump(out):
            return np.asarray(out)
    """, "src/repro/launch/fake.py") == []


def test_rule_wallclock_in_jit_flags_and_twin_passes():
    bad = _lint("""
        import time, jax
        def _inner(x):
            return x * time.time()
        @jax.jit
        def step(x):
            return _inner(x)
    """)
    assert [f.rule for f in bad] == ["wallclock-in-jit"]
    good = _lint("""
        import time, jax
        @jax.jit
        def step(x):
            return x * 2.0
        def drive(x):
            t0 = time.time()      # host side: fine
            return step(x), time.time() - t0
    """)
    assert good == []


def test_pragma_escape_suppresses_named_rule_only():
    src = """
        def host_side(buf, x):
            # lint: allow(eager-scatter) staged upload
            return buf.at[0].set(x)
    """
    assert _lint(src) == []
    wrong_rule = """
        def host_side(buf, x):
            # lint: allow(iota-gather)
            return buf.at[0].set(x)
    """
    assert [f.rule for f in _lint(wrong_rule)] == ["eager-scatter"]


def test_repo_is_lint_clean():
    from pathlib import Path
    findings = lint.lint_repo(Path(REPO_ROOT))
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_cli_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--ast"],
        cwd=REPO_ROOT, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO_ROOT, "src"),
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    assert "AST lint: clean" in out.stdout


# -- mutation demonstrations --------------------------------------------------


def test_realiased_init_telemetry_fails_donation_probe(monkeypatch):
    """Re-introduce the PR-2 bug: one zeros buffer aliased into all three
    TelemetryState fields.  The compiled alias map STILL lists every leaf
    as may-alias (the static clause passes), but executing the donating
    step must trip the runtime probe — exactly how the bug originally
    surfaced."""
    from repro.models import lstm_am
    from repro.serving import BatchedSpartusEngine, EngineConfig
    from repro.serving import telemetry as tele

    def aliased_init(n_layers, n_slots):
        z = jnp.zeros((n_layers, n_slots), jnp.float32)
        return tele.TelemetryState(nnz_sum=z, overflow_steps=z, steps=z)

    monkeypatch.setattr(tele, "init_telemetry", aliased_init)
    cfg = lstm_am.LSTMAMConfig(input_dim=caselib.INPUT_DIM,
                               hidden_dim=caselib.HIDDEN, n_layers=2,
                               n_classes=caselib.CLASSES)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg),
        gamma=caselib.GAMMA, m=caselib.M)
    engine = BatchedSpartusEngine(params, cfg, EngineConfig(
        theta=caselib.THETA, gamma=caselib.GAMMA, m=caselib.M,
        capacity_frac=1.0))

    def build():
        state = engine.init_state(4)
        frames = jax.random.normal(jax.random.key(3),
                                   (4, 8, caselib.INPUT_DIM), jnp.float32)
        return BuiltCase(fn=engine._step_frames,
                         args=(state, frames, jnp.ones((4,), bool),
                               jnp.zeros((4,), bool)),
                         kwargs={}, donate_argnums=(0,))

    case = caselib.ContractCase("step_frames/aliased-telemetry",
                                "step_frames", build)
    report = contracts.check_case(case)
    assert not report.ok
    assert [v.clause for v in report.violations] == ["donation"]
    assert "donate" in report.violations[0].message
    # the static alias map alone could NOT have caught it:
    assert report.alias_entries == report.donated_leaves


IOTA_REVERT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.analysis import cases as caselib
    from repro.analysis import contracts, hlo
    from repro.kernels import ops

    engine = caselib._engine()
    feats = caselib._feats(8)

    def lower():
        built = caselib.built_pool_chunk(engine, feats, capacity=8,
                                         n_devices=4)
        return hlo.compiled_text(built.fn, *built.args, **built.kwargs)

    healthy = hlo.count_collectives(lower())
    contract = contracts.get_contract("step_chunk")
    healthy_viol = [v.clause for v in contracts.check_hlo(
        contract, lower()) if v.clause == "no_collectives"]

    # revert to the pre-PR-5 gather: batch-iota advanced indexing.  GSPMD
    # cannot keep it local per shard, so the compiled sharded scan grows
    # an all-gather + all-reduce per iteration:
    def iota_gather(frames, cursor):
        t_buf = frames.shape[1]
        idx = jnp.minimum(cursor, t_buf - 1).astype(jnp.int32)
        return frames[jnp.arange(frames.shape[0]), idx]

    ops.gather_frames = iota_gather
    engine._step_chunk = jax.jit(engine._step_chunk_impl,
                                 static_argnames=("n_frames",),
                                 donate_argnums=(0, 5))
    mutated_txt = lower()
    mutated = hlo.count_collectives(mutated_txt)
    mutated_viol = [v.clause for v in contracts.check_hlo(
        contract, mutated_txt) if v.clause == "no_collectives"]
    print(json.dumps({
        "devices": len(jax.devices()),
        "healthy_collectives": healthy,
        "healthy_violations": healthy_viol,
        "mutated_collectives": mutated,
        "mutated_violations": mutated_viol,
    }))
""")


@pytest.mark.slow
def test_iota_gather_revert_breaks_sharded_contract():
    """Re-introduce the PR-5 bug in a 4-emulated-device subprocess and
    compile the REAL sharded chunk both ways: the take_along_axis gather
    must check clean, the iota revert must make the no_collectives clause
    fire (GSPMD inserts collectives into the scan)."""
    env = {"PYTHONPATH": os.path.join(REPO_ROOT, "src"),
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", IOTA_REVERT_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=REPO_ROOT, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["devices"] == 4
    assert payload["healthy_collectives"] == 0
    assert payload["healthy_violations"] == []
    assert payload["mutated_collectives"] > 0
    assert payload["mutated_violations"] == ["no_collectives"]


# -- hlo helper unit coverage -------------------------------------------------


def test_op_histogram_counts_fusion_bodies_and_folds_versions():
    h = hlo.op_histogram(_CANNED_OK)
    assert h["transpose"] == 1      # inside the fusion computation
    assert h["add"] == 1            # add.0 folded onto 'add'
    assert h["fusion"] == 1


def test_collective_and_host_transfer_tokens_match_legacy_pins():
    """The analyzer's token lists are the SAME strings the PR-5/PR-6
    test pins greped for — migrating the tests must not have changed
    what counts as a violation — plus the FFI callback target that
    jax 0.9 emits for the same host callback."""
    assert hlo.COLLECTIVE_TOKENS == (
        "all-reduce", "all-gather", "collective-permute", "all-to-all",
        "reduce-scatter")
    assert hlo.HOST_TRANSFER_TOKENS == (
        "outfeed", "infeed", "xla_python_cpu_callback",
        "xla_ffi_python_cpu_callback", "host_callback",
        "SendToHost", "RecvFromHost")
