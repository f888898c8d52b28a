"""Static-analysis subsystem (paddle_tpu.analysis): two passes.

Pass 1 — the AOT program auditor proves compile-time invariants on the
actual jitted programs (donation aliasing, no host callbacks, static
shapes, dtype policy, collective census, HBM budget), hooked into
``jit.CompiledTrainStep`` and the serving engines behind
``FLAGS_program_audit``.  Pass 2 — the TPU-hazard linter (PT001-PT006)
gates the source tree against the idioms that cost a bench run to
discover dynamically.  Both must catch seeded violations AND pass clean
over the real train-step / serving programs — the same double gate
``scripts/check_counters.py`` enforces in CI."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.jit as pjit
import paddle_tpu.nn as nn
from paddle_tpu.analysis import lint as ptlint
from paddle_tpu.analysis import program_audit as paudit
from paddle_tpu.core import flags as cflags
from paddle_tpu.profiler import counters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def audit_mode():
    """Set FLAGS_program_audit for one test; restore 'off' + forget the
    audited-name dedupe set afterwards (process-global state)."""
    paudit.reset_audited()

    def _set(mode):
        cflags.set_flags({"FLAGS_program_audit": mode})

    try:
        yield _set
    finally:
        cflags.set_flags({"FLAGS_program_audit": "off"})
        paudit.reset_audited()


def _mse(m, x, y):
    return ((m(x) - y) ** 2).mean()


def _train_step(**kw):
    paddle.seed(7)
    net = nn.Linear(8, 4)
    opt = paddle.optimizer.Adam(parameters=net.parameters(),
                                learning_rate=1e-2)
    step = pjit.CompiledTrainStep(net, _mse, opt, **kw)
    x = paddle.randn([16, 8])
    y = paddle.randn([16, 4])
    return step, x, y


# ---------------------------------------------------------------------------
# linter: one positive + one suppressed case per rule
# ---------------------------------------------------------------------------

def _lint(src, **kw):
    kw.setdefault("check_counters", False)
    return ptlint.lint_source(src, path="paddle_tpu/fake.py", **kw)


def _active(src, **kw):
    return [f for f in _lint(src, **kw) if not f.suppressed]


class TestLintRules:
    def test_pt001_host_sync_in_traced(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    v = x.mean().item()\n"
            "    return float(x.sum())\n")
        rules = [f.rule for f in _active(src)]
        assert rules.count("PT001") == 2

    def test_pt001_shape_reads_are_fine(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    n = float(x.shape[0])\n"
            "    k = int(len(x))\n"
            "    return x / n * k\n")
        assert not _active(src)

    def test_pt001_transitive_callee(self):
        # helper called from a jitted fn is traced too
        src = (
            "import jax\n"
            "def helper(x):\n"
            "    return x.numpy()\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return helper(x)\n")
        assert [f.rule for f in _active(src)] == ["PT001"]

    def test_pt001_untraced_code_not_flagged(self):
        src = ("def host_fn(x):\n"
               "    return float(x.mean())\n")
        assert not _active(src)

    def test_pt002_compile_and_discard(self):
        src = ("import jax\n"
               "def f(g, x):\n"
               "    return jax.jit(g)(x)\n")
        assert [f.rule for f in _active(src)] == ["PT002"]

    def test_pt002_unhashable_cache_key(self):
        src = ("def lookup(self, shapes):\n"
               "    return self._jits[[s for s in shapes]]\n")
        assert [f.rule for f in _active(src)] == ["PT002"]

    def test_pt003_donation_ternary_trap(self):
        src = ("import jax\n"
               "def mk(fn, donate):\n"
               "    return jax.jit(fn,\n"
               "        donate_argnums=donate + (7,) if donate else ())\n")
        assert [f.rule for f in _active(src)] == ["PT003"]

    def test_pt003_parenthesized_fix_clean(self):
        # the shape the repo actually uses after the fix
        src = ("import jax\n"
               "def mk(fn, donate):\n"
               "    return jax.jit(fn,\n"
               "        donate_argnums=donate + ((7,) if donate else ()))\n")
        assert not _active(src)

    def test_pt003_plain_ternary_clean(self):
        # no binary operand in either branch — unambiguous, allowed
        src = ("import jax\n"
               "def mk(fn, flag):\n"
               "    return jax.jit(fn,\n"
               "        donate_argnums=(0, 1, 2) if flag else ())\n")
        assert not _active(src)

    def test_pt004_nondeterminism_in_traced(self):
        src = ("import jax, time\n"
               "import numpy as np\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    t = time.time()\n"
               "    r = np.random.rand()\n"
               "    return x * t + r\n")
        rules = [f.rule for f in _active(src)]
        assert rules.count("PT004") == 2

    def test_pt005_dispatch_under_lock(self):
        src = ("import jax.numpy as jnp\n"
               "def run(self, x):\n"
               "    with self._lock:\n"
               "        dec = self._pdecode(1)\n"
               "        out = dec(x)\n"
               "        s = jnp.sum(out)\n"
               "    return s\n")
        rules = [f.rule for f in _active(src)]
        assert rules.count("PT005") == 2

    def test_pt005_dispatch_outside_lock_clean(self):
        src = ("import jax.numpy as jnp\n"
               "def run(self, x):\n"
               "    with self._lock:\n"
               "        dec = self._pdecode(1)\n"
               "    return jnp.sum(dec(x))\n")
        assert not _active(src)

    def test_pt006_undocumented_counter(self):
        pats = ptlint.documented_counter_patterns()
        src = ("from paddle_tpu.profiler import counters\n"
               "counters.inc('totally.bogus_name')\n"
               "counters.inc('jit.steps')\n"
               "counters.inc(f'dist.{op}')\n")
        active = _active(src, check_counters=True, counter_patterns=pats)
        assert [f.rule for f in active] == ["PT006"]
        assert "totally.bogus_name" in active[0].message

    def test_pt006_analysis_counters_documented(self):
        # the auditor's own counters must pass its own lint
        pats = ptlint.documented_counter_patterns()
        for name in ("analysis.audits", "analysis.findings",
                     "analysis.findings.donation-dropped",
                     "analysis.findings.host-callback"):
            assert ptlint._counter_name_ok(name, False, pats), name

    def test_suppression_with_reason(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    # ptlint: disable=PT001 reason=\"test fixture\"\n"
               "    return x.numpy()\n")
        finds = _lint(src)
        assert len(finds) == 1 and finds[0].suppressed
        assert finds[0].reason == "test fixture"
        assert not _active(src)

    def test_suppression_without_reason_stays_active(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    return x.numpy()  # ptlint: disable=PT001\n")
        assert [f.rule for f in _active(src)] == ["PT001"]

    def test_fingerprint_ignores_line_numbers(self):
        a = ptlint.LintFinding(rule="PT001", path="p.py", line=3, col=0,
                               message="m", snippet="return x.numpy()")
        b = ptlint.LintFinding(rule="PT001", path="p.py", line=99, col=4,
                               message="m", snippet="return x.numpy()")
        assert ptlint.fingerprint(a) == ptlint.fingerprint(b)


# ---------------------------------------------------------------------------
# linter: the repo itself must be clean vs the checked-in baseline
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def repo_findings():
    return ptlint.lint_paths(ptlint.default_targets(ROOT), root=ROOT)


class TestRepoSweep:
    def test_repo_has_no_new_findings(self, repo_findings):
        base = ptlint.load_baseline(
            os.path.join(ROOT, "scripts", "lint_baseline.json"))
        new = [f for f in repo_findings
               if not f.suppressed and ptlint.fingerprint(f) not in base]
        assert not new, "NEW lint findings:\n" + "\n".join(
            f.format() for f in new)

    def test_all_suppressions_carry_reasons(self, repo_findings):
        for f in repo_findings:
            if f.suppressed:
                assert f.reason, f.format()

    @pytest.mark.slow
    def test_lint_cli_check_exits_zero(self):
        env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "lint_tpu.py"),
             "--check"],
            capture_output=True, text=True, env=env, timeout=240)
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# auditor: seeded broken fixtures must be caught by the right rule
# ---------------------------------------------------------------------------

class TestAuditorFixtures:
    def test_host_callback_caught(self):
        def f(x):
            return jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        rep = paudit.audit_program("t.cb", jax.jit(f), jnp.ones((4,)),
                                   compile_program=False)
        assert not rep.ok
        assert {f.rule for f in rep.findings} == {"host-callback"}
        assert rep.primitive_counts.get("pure_callback", 0) >= 1

    def test_dropped_donation_caught(self):
        # sum() consumes the donated buffer without any same-shaped
        # output to alias it to — the drop must be a hard finding
        fn = jax.jit(lambda a: jnp.sum(a), donate_argnums=(0,))
        rep = paudit.audit_program("t.drop", fn, jnp.ones((4, 4)),
                                   donate_argnums=(0,),
                                   compile_program=False)
        assert any(f.rule == "donation-dropped" for f in rep.findings)
        assert rep.donated_leaves == 1 and rep.aliased_leaves == 0

    def test_dynamic_shape_caught(self):
        from jax import export as jexport
        bdim = jexport.symbolic_shape("b, 4")
        rep = paudit.audit_program(
            "t.dyn", jax.jit(lambda z: z * 2),
            jax.ShapeDtypeStruct(bdim, jnp.float32),
            compile_program=False)
        assert any(f.rule == "dynamic-shape" for f in rep.findings)

    def test_f64_promotion_caught(self):
        jax.config.update("jax_enable_x64", True)
        try:
            rep = paudit.audit_program(
                "t.f64", jax.jit(lambda x: x * 2.0),
                jnp.ones((4,), jnp.float64), compile_program=False)
        finally:
            jax.config.update("jax_enable_x64", False)
        assert any(f.rule == "f64-promotion" for f in rep.findings)

    def test_collective_census_caught(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:2]), ("i",))
        # default check_vma=True: jax 0.9.0 traces this psum as
        # `psum_invariant` — the name the auditor's census must know
        fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "i"),
                                   mesh=mesh, in_specs=P("i"),
                                   out_specs=P()))
        rep = paudit.audit_program("t.coll", fn, jnp.ones((2,)),
                                   expect_no_collectives=True,
                                   compile_program=False)
        assert any(f.rule == "collective-budget" for f in rep.findings)
        assert rep.collective_counts.get("psum_invariant", 0) >= 1
        # mesh programs with collectives *allowed* report the census only
        rep2 = paudit.audit_program("t.coll.ok", fn, jnp.ones((2,)),
                                    expect_no_collectives=False,
                                    compile_program=False)
        assert rep2.ok and rep2.collective_counts.get(
            "psum_invariant", 0) >= 1

    def test_hbm_budget_caught(self):
        fn = jax.jit(lambda x: x @ x)
        rep = paudit.audit_program("t.hbm", fn, jnp.ones((64, 64)),
                                   hbm_budget_bytes=1)
        assert any(f.rule == "hbm-budget" for f in rep.findings)

    def test_counters_and_flight_fed(self, audit_mode):
        before = counters.snapshot()
        fn = jax.jit(lambda a: jnp.sum(a), donate_argnums=(0,))
        paudit.audit_program("t.counted", fn, jnp.ones((4, 4)),
                             donate_argnums=(0,), compile_program=False)
        d = counters.delta(before)
        assert d.get("analysis.audits") == 1
        assert d.get("analysis.findings.donation-dropped") == 1


# ---------------------------------------------------------------------------
# auditor: the real programs must pass clean (the double gate)
# ---------------------------------------------------------------------------

class TestAuditorCleanPrograms:
    def test_train_step_clean_under_enforce(self, audit_mode):
        audit_mode("enforce")
        step, x, y = _train_step(metrics=True)
        before = counters.snapshot()
        step(x, y)  # fresh compile -> audit at the compile site; must not raise
        d = counters.delta(before)
        assert d.get("analysis.audits", 0) >= 1
        assert d.get("analysis.findings", 0) == 0
        # dedupe: steady-state steps never re-audit
        before = counters.snapshot()
        step(x, y)
        assert counters.delta(before).get("analysis.audits", 0) == 0

    def test_fused_window_clean_under_enforce(self, audit_mode):
        audit_mode("enforce")
        from paddle_tpu.io import StackingPrefetcher
        step, x, y = _train_step(metrics=True, fused_steps=2)
        before = counters.snapshot()
        # window 1 falls back to single-step (accumulators not yet
        # materialized); window 2 compiles + audits the fused program
        for w in StackingPrefetcher(iter([(x, y)] * 4), k=2):
            step(*w)
        d = counters.delta(before)
        assert d.get("jit.fused_windows", 0) >= 1
        assert d.get("analysis.audits", 0) >= 2  # step + window programs
        assert d.get("analysis.findings", 0) == 0

    def test_serving_programs_clean_under_enforce(self, audit_mode):
        audit_mode("enforce")
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving import LLMEngine
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        paddle.seed(31)
        m = GPTForCausalLM(cfg)
        m.eval()
        eng = LLMEngine(m, max_slots=2, max_seq_len=32, min_bucket=4)
        before = counters.snapshot()
        outs = eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
        d = counters.delta(before)
        assert len(outs) == 2
        assert d.get("analysis.audits", 0) >= 2  # prefill + decode at least
        assert d.get("analysis.findings", 0) == 0


# ---------------------------------------------------------------------------
# donation regression: the macc buffer must alias whenever the carry donates
# (the PT003 ternary at the _make_jit sites used to make this easy to lose)
# ---------------------------------------------------------------------------

class TestMaccDonation:
    def _compiled_step_args(self, **kw):
        step, x, y = _train_step(metrics=True, **kw)
        step(x, y)
        params, buffers, opt_state, sstate, rng_key = step._state
        cargs = (params, buffers, opt_state, step._lr_dev, rng_key, sstate,
                 step._strip((x, y)), step._macc)
        return step, cargs

    def test_step_macc_aliased_when_carry_donated(self):
        step, cargs = self._compiled_step_args()
        jit_fn = step._jits[(False, True)]
        # the macc dict is arg 7: all 4 of its leaves must alias outputs
        rep = paudit.audit_program("t.macc", jit_fn, *cargs,
                                   donate_argnums=(7,),
                                   compile_program=False)
        assert rep.ok, [f.message for f in rep.findings]
        assert rep.donated_leaves == len(step._MACC_KEYS) == 4
        # and the full carry (params/buffers/opt-state) + macc donation holds
        rep = paudit.audit_program("t.macc.full", jit_fn, *cargs,
                                   donate_argnums=(0, 1, 2, 7),
                                   compile_program=False)
        assert rep.ok, [f.message for f in rep.findings]
        assert rep.aliased_leaves >= rep.donated_leaves > 4

    def test_window_macc_aliased_when_carry_donated(self, audit_mode):
        # the fused-window program audits (0,1,2,7) at its compile site;
        # enforce mode turns any dropped macc leaf into a raise here
        audit_mode("enforce")
        from paddle_tpu.io import StackingPrefetcher
        step, x, y = _train_step(metrics=True, fused_steps=2)
        before = counters.snapshot()
        for w in StackingPrefetcher(iter([(x, y)] * 4), k=2):
            step(*w)
        assert (False, 2, True) in step._fused_jits
        with paudit._AUDITED_LOCK:
            audited = set(paudit._AUDITED)
        assert "jit.window[check=0,k=2,metrics=1]" in audited
        assert counters.delta(before).get(
            "analysis.findings.donation-dropped", 0) == 0

    def test_no_aliasing_without_donation(self):
        step, cargs = self._compiled_step_args(donate=False)
        jit_fn = step._jits[(False, True)]
        txt = jit_fn.trace(*cargs).lower().as_text()
        aliased, total = paudit._aliased_arg_indices(txt)
        assert aliased == set()
        assert total == sum(len(jax.tree_util.tree_leaves(a))
                            for a in cargs)


# ---------------------------------------------------------------------------
# maybe_audit: flag modes + once-per-program dedupe
# ---------------------------------------------------------------------------

class TestMaybeAudit:
    BROKEN = staticmethod(
        lambda: jax.jit(lambda a: jnp.sum(a), donate_argnums=(0,)))

    def test_off_is_noop(self, audit_mode):
        audit_mode("off")
        before = counters.snapshot()
        out = paudit.maybe_audit("t.off", self.BROKEN(), jnp.ones((4, 4)),
                                 donate_argnums=(0,), compile_program=False)
        assert out is None
        assert counters.delta(before).get("analysis.audits", 0) == 0

    def test_warn_files_findings_without_raising(self, audit_mode):
        audit_mode("warn")
        before = counters.snapshot()
        rep = paudit.maybe_audit("t.warn", self.BROKEN(), jnp.ones((4, 4)),
                                 donate_argnums=(0,), compile_program=False)
        assert rep is not None and not rep.ok
        d = counters.delta(before)
        assert d.get("analysis.findings.donation-dropped") == 1

    def test_enforce_raises_at_compile_site(self, audit_mode):
        audit_mode("enforce")
        with pytest.raises(paudit.ProgramAuditError) as ei:
            paudit.maybe_audit("t.enforce", self.BROKEN(), jnp.ones((4, 4)),
                               donate_argnums=(0,), compile_program=False)
        assert "donation-dropped" in str(ei.value)
        assert ei.value.report.name == "t.enforce"

    def test_each_name_audited_once(self, audit_mode):
        audit_mode("warn")
        fn = jax.jit(lambda x: x + 1)
        before = counters.snapshot()
        first = paudit.maybe_audit("t.once", fn, jnp.ones((2,)),
                                   compile_program=False)
        second = paudit.maybe_audit("t.once", fn, jnp.ones((2,)),
                                    compile_program=False)
        assert first is not None and second is None
        assert counters.delta(before).get("analysis.audits") == 1

    def test_package_export(self):
        assert paddle.analysis.lint is ptlint
        assert paddle.analysis.program_audit is paudit
