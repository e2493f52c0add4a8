"""Static-analysis gate tests: each seeded defect is caught, the repo is
clean (docs/analysis.md).

Two halves. The seeded-defect fixtures feed the auditor/lint a program
or source snippet containing exactly one planted violation — a tick
exceeding its dispatch ceiling, a silently-dropped donation, an fp64
leak, a host callback inside a scan body, an ``id()``-keyed cache, an
unguarded ``Lane`` field write — and assert a finding naming the
entrypoint/field. The clean-repo tests run the same passes over the
real tree and assert zero findings, which is what CI's ``analysis`` leg
enforces (tools/check_programs.py, tools/check_threads.py)."""

import functools
import textwrap

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_audit, thread_lint
from repro.analysis.jaxpr_audit import TracedEntry
from repro.analysis.thread_lint import ClassDiscipline
from repro.kernels import ops


def _checks(findings):
    return {f.check for f in findings}


# --- seeded defects: program auditor -------------------------------------------


def test_dispatch_budget_excess_caught():
    """A tick making four stacked dispatches against a ceiling of three
    must fail, naming the entrypoint — a regen cannot lift ceilings."""
    def fat_tick(x):
        for _ in range(4):
            ops.record_dispatch("predict_heads")
            x = x + 1.0
        return x

    entry = TracedEntry(fn=fat_tick, args=(jnp.zeros((3,), jnp.float32),),
                        max_dispatch={"predict_heads": 3})
    metrics, findings = jaxpr_audit.audit_entry("fat_tick", entry)
    assert metrics.dispatches == {"predict_heads": 4}
    assert [f.check for f in findings] == ["dispatch-budget"]
    assert findings[0].entry == "fat_tick"
    assert "4" in findings[0].message and "3" in findings[0].message


def test_dropped_donation_caught():
    """donate_argnums leaves that XLA cannot alias (shape/dtype mismatch
    with every output) are silently copied — the auditor must flag the
    drop rather than trust the declaration."""
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(a, b):
        # 'a' aliases the first output; 'b' reduces to a scalar and can
        # alias nothing — a dropped donation
        return a + 1.0, b.sum()

    entry = TracedEntry(fn=step,
                        args=(jnp.zeros((4,), jnp.float32),
                              jnp.zeros((5,), jnp.float32)),
                        donate=(0, 1))
    metrics, findings = jaxpr_audit.audit_entry("leaky_step", entry)
    assert "donation" in _checks(findings)
    assert metrics.donated == 1  # only 'a' actually aliased, not 2
    assert any(f.entry == "leaky_step" for f in findings)


def test_clean_donation_passes():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(a, b):
        return a + b, b.sum()

    entry = TracedEntry(fn=step,
                        args=(jnp.zeros((4,), jnp.float32),
                              jnp.zeros((4,), jnp.float32)),
                        donate=(0,))
    metrics, findings = jaxpr_audit.audit_entry("ok_step", entry)
    assert findings == []
    assert metrics.donated == 1


def test_fp64_promotion_caught():
    """An fp64 aval anywhere in a traced hot-path body is a finding."""
    def promoting(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        entry = TracedEntry(fn=promoting,
                            args=(jnp.zeros((3,), jnp.float32),))
        _, findings = jaxpr_audit.audit_entry("wide_tick", entry)
    assert "fp64-promotion" in _checks(findings)
    assert any("float64" in f.message for f in findings)


def test_callback_in_scan_caught():
    """A pure_callback inside a scan body host-syncs every tick."""
    def body(carry, _):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), carry)
        return carry + y, y

    def run(x0):
        return jax.lax.scan(body, x0, None, length=4)

    entry = TracedEntry(fn=run, args=(jnp.float32(0.0),))
    _, findings = jaxpr_audit.audit_entry("chatty_scan", entry)
    cb = [f for f in findings if f.check == "host-callback"]
    assert cb and "scan body" in cb[0].message


def test_id_keyed_cache_caught():
    src = textwrap.dedent("""
        def _key(self, surrogate, b):
            return (id(surrogate), b)
    """)
    findings = jaxpr_audit.check_cache_key_source(
        src, required=("b",), name="bad-cache")
    assert [f.check for f in findings] == ["cache-key"]
    assert "id(" in findings[0].message
    assert findings[0].entry == "bad-cache"


def test_missing_cache_key_field_caught():
    src = "def _key(self, b):\n    return (b,)\n"
    findings = jaxpr_audit.check_cache_key_source(
        src, required=("b", "structure_key"), name="narrow-cache")
    assert len(findings) == 1
    assert "structure_key" in findings[0].message


def test_env_read_outside_ops_caught(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "rogue.py").write_text(
        "import os\n"
        "SMOKE = os.environ.get('REPRO_BENCH_SMOKE')\n"
        "DIR = os.environ['REPRO_BENCH_DIR']\n"
        "os.environ['XLA_FLAGS'] = 'x'\n")  # a write: allowed
    findings = jaxpr_audit.check_env_discipline(root=tmp_path)
    assert len(findings) == 2
    assert all(f.check == "env-discipline" for f in findings)
    assert all("rogue.py" in f.entry for f in findings)


# --- seeded defects: concurrency lint ------------------------------------------

_LANE_TABLE = {"Lane": ClassDiscipline(
    lock="_lock",
    driver=frozenset({"_carries"}),
    driver_write=frozenset({"g"}),
    locked=frozenset({"_queue"}),
    init=frozenset({"engine"}),
    driver_methods=frozenset({"step"}),
)}


def _lint(src, table=None):
    return thread_lint.lint_source(textwrap.dedent(src),
                                   table or _LANE_TABLE, "fixture.py")


def test_unguarded_lane_field_write_caught():
    findings = _lint("""
        class Lane:
            def submit(self, req):
                self._carries = req      # driver-only state, wrong thread
            def step(self):
                self._carries = None     # fine: driver method
    """)
    assert len(findings) == 1
    f = findings[0]
    assert f.check == "thread-affinity"
    assert "_carries" in f.message and "Lane.submit" in f.entry


def test_locked_field_outside_lock_caught():
    findings = _lint("""
        class Lane:
            def submit(self, req):
                self._queue.append(req)
            def drain(self):
                with self._lock:
                    return list(self._queue)
    """)
    assert len(findings) == 1
    assert findings[0].check == "unguarded-state"
    assert "_queue" in findings[0].message


def test_blocking_call_under_lock_caught():
    findings = _lint("""
        class Lane:
            def step(self):
                with self._lock:
                    self.engine.compile()
    """)
    assert len(findings) == 1
    assert findings[0].check == "blocking-under-lock"
    assert "compile" in findings[0].message


def test_callback_under_lock_caught():
    """RequestHandle._push fires the user's on_chunk — never under a
    server lock (user code re-entering submit() would deadlock)."""
    findings = _lint("""
        class Lane:
            def step(self):
                with self._lock:
                    handle._push(chunk)
    """)
    assert [f.check for f in findings] == ["blocking-under-lock"]
    assert "_push" in findings[0].message


def test_unannotated_field_caught():
    """Table completeness is load-bearing: a new field with no declared
    locking discipline is itself a finding."""
    findings = _lint("""
        class Lane:
            def step(self):
                self.scratch = 1
    """)
    assert [f.check for f in findings] == ["unannotated-field"]
    assert "scratch" in findings[0].message


def test_driver_write_racy_read_tolerated():
    findings = _lint("""
        class Lane:
            def stats(self):
                return self.g            # racy read: tolerated
            def submit(self):
                self.g = 2.0             # foreign write: flagged
    """)
    assert len(findings) == 1
    assert "g" in findings[0].message and "submit" in findings[0].entry


def test_condition_wait_exempt_under_lock():
    table = {"Srv": ClassDiscipline(
        lock="_lock", lock_aliases=frozenset({"_wake"}),
        locked=frozenset({"_queues"}))}
    findings = _lint("""
        class Srv:
            def _drive(self):
                with self._wake:
                    if not self._queues:
                        self._wake.wait(0.1)
    """, table)
    assert findings == []


def test_cross_object_driver_store_caught():
    findings = _lint("""
        class Lane:
            def submit(self, lane):
                lane.g = 1.0
    """)
    assert len(findings) == 1
    assert findings[0].check == "thread-affinity"


# --- the repo itself is clean --------------------------------------------------


def test_repo_thread_lint_clean():
    assert thread_lint.run_lint() == []


def test_repo_cache_keys_clean():
    assert jaxpr_audit.check_cache_keys() == []


def test_repo_env_discipline_clean():
    assert jaxpr_audit.check_env_discipline() == []


def test_repo_program_audit_clean():
    """The full trace-time audit against the frozen budgets — exactly
    what CI's analysis leg runs via tools/check_programs.py."""
    findings = jaxpr_audit.run_audit(jaxpr_audit.load_budgets())
    assert findings == [], "\n".join(str(f) for f in findings)


def test_frozen_budgets_cover_all_entrypoints():
    frozen = jaxpr_audit.load_budgets()
    # builders register at jaxpr_audit import time (module-level decorators)
    registered = set(ops.registered_entrypoints())
    assert registered == set(frozen)
    # the two headline ceilings, asserted against the frozen file itself
    assert sum(frozen["tick_fused_standalone"]["dispatches"].values()) <= 3
    assert frozen["tick_megakernel"]["dispatches"] == {"megakernel_step": 1}
    assert frozen["tick_fused_annotation"]["dispatches"] == {
        "predict_heads": 1}


def test_dispatch_scope_nests_and_restores():
    with ops.dispatch_scope() as outer:
        ops.record_dispatch("a")
        with ops.dispatch_scope() as inner:
            ops.record_dispatch("b")
        ops.record_dispatch("a")
    assert outer == ["a", "a"] and inner == ["b"]
    ops.record_dispatch("dropped")  # no active scope: a no-op
