"""Tier-1 wiring of tools/graftcheck.py: the JAX-aware static-analysis
suite.  Each pass is proven by a known-bad fixture (a seeded
use-after-donate, a tracer bool, an unlocked guarded write, an
undocumented env var must all FLAG), and the real package must come out
clean: zero unsuppressed findings."""

import os
import sys
import textwrap
import time

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
))

import graftcheck  # noqa: E402


def _mi(src: str, rel: str = "fixture.py") -> "graftcheck.ModuleInfo":
    return graftcheck.ModuleInfo(rel, rel, textwrap.dedent(src))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- donation


def test_use_after_donate_flags():
    mi = _mi(
        """
        import jax

        def train(state, batch):
            return state

        def run(state, batch):
            step = jax.jit(train, donate_argnums=(0,))
            out = step(state, batch)
            return state  # reads the donated buffer
        """
    )
    fs = graftcheck.check_donation(mi)
    assert any(f.rule == "use-after-donate" for f in fs), _rules(fs)


def test_rebind_idiom_is_clean():
    mi = _mi(
        """
        import jax

        def train(state, batch):
            return state

        def run(state, batches):
            step = jax.jit(train, donate_argnums=(0,))
            for b in batches:
                state = step(state, b)
            return state
        """
    )
    fs = [f for f in graftcheck.check_donation(mi)
          if f.rule == "use-after-donate"]
    assert not fs, [f.render() for f in fs]


def test_getter_idiom_use_after_donate():
    mi = _mi(
        """
        import jax

        class Engine:
            def _insert_fn(self):
                if "insert" not in self._fns:
                    def insert(dstate, row):
                        return dstate
                    self._fns["insert"] = jax.jit(
                        insert, donate_argnums=(0,)
                    )
                return self._fns["insert"]

            def bad(self, row):
                out = self._insert_fn()(self._dstate, row)
                return self._dstate  # donated above, never rebound

            def good(self, row):
                self._dstate = self._insert_fn()(self._dstate, row)
                return self._dstate
        """
    )
    fs = [f for f in graftcheck.check_donation(mi)
          if f.rule == "use-after-donate"]
    assert len(fs) == 1, [f.render() for f in fs]
    assert "self._dstate" in fs[0].message


def test_for_target_and_with_as_clear_taint():
    # rebinds through loop targets and `with ... as` are rebinds too
    mi = _mi(
        """
        import jax

        def train(state, batch):
            return state

        def run(state, batches, opener):
            step = jax.jit(train, donate_argnums=(0,))
            out = step(state, batches[0])
            for state in batches:
                pass
            with opener() as state:
                pass
            return state  # rebound twice since the donation
        """
    )
    fs = [f for f in graftcheck.check_donation(mi)
          if f.rule == "use-after-donate"]
    assert not fs, [f.render() for f in fs]


def test_donation_vector_consistency():
    mi = _mi(
        """
        import jax

        def dispatch(variables, dstate):
            return dstate

        fn = jax.jit(dispatch)  # carry not donated: must flag
        ok = jax.jit(dispatch, donate_argnums=(1,))
        """
    )
    fs = [f for f in graftcheck.check_donation(mi)
          if f.rule == "donation-vector"]
    assert len(fs) == 1, [f.render() for f in fs]


def test_donation_sharding_flags_reshard_of_donated_name():
    """The mesh-aware rule: resharding a donated carry name
    (device_put / with_sharding_constraint) in the same function that
    donates it flags — order-insensitive, because loop bodies donate
    and reuse across iterations."""
    mi = _mi(
        """
        import jax

        def dispatch(variables, dstate):
            return dstate

        class Eng:
            def _dispatch_fn(self):
                return jax.jit(dispatch, donate_argnums=(1,))

            def loop(self, sharding):
                while True:
                    self._dstate = jax.device_put(
                        self._dstate, sharding
                    )
                    self._dstate = self._dispatch_fn()(
                        self.variables, self._dstate
                    )

            def loop2(self, sharding):
                while True:
                    self._dstate = jax.lax.with_sharding_constraint(
                        self._dstate, sharding
                    )
                    self._dstate = self._dispatch_fn()(
                        self.variables, self._dstate
                    )
        """
    )
    fs = [f for f in graftcheck.check_donation(mi)
          if f.rule == "donation-sharding"]
    assert len(fs) == 2, [f.render() for f in fs]
    assert "device_put" in fs[0].message


def test_donation_sharding_clean_when_resharding_other_names():
    """In-trace constraints on NON-donated values (the engine's
    _constrain_carry on the traced output) and construction-time
    placement in a DIFFERENT function stay clean."""
    mi = _mi(
        """
        import jax

        def dispatch(variables, dstate):
            out = dict(dstate)
            out = jax.lax.with_sharding_constraint(out, None)
            return out

        class Eng:
            def _dispatch_fn(self):
                return jax.jit(dispatch, donate_argnums=(1,))

            def fresh(self, sharding):
                self._dstate = jax.device_put(self.init(), sharding)

            def loop(self):
                while True:
                    self._dstate = self._dispatch_fn()(
                        self.variables, self._dstate
                    )
        """
    )
    fs = [f for f in graftcheck.check_donation(mi)
          if f.rule == "donation-sharding"]
    assert not fs, [f.render() for f in fs]


# ---------------------------------------------------------------- trace


def test_trace_hazards_flag():
    mi = _mi(
        """
        import time
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step(x):
            y = jnp.sum(x)
            if y > 0:            # tracer-control-flow
                pass
            t = time.time()      # traced-time
            z = float(y)         # host-sync
            w = np.asarray(y)    # host-sync
            v = y.item()         # host-sync
            return x

        f = jax.jit(step)
        """
    )
    fs = graftcheck.check_trace(mi)
    rules = [f.rule for f in fs]
    assert rules.count("tracer-control-flow") == 1, rules
    assert rules.count("traced-time") == 1, rules
    assert rules.count("host-sync") == 3, rules


def test_static_knob_params_are_not_tracers():
    # static Python config rides traced functions as plain params all
    # over the repo (top_k, causal, chunk widths) — must stay clean
    mi = _mi(
        """
        import jax
        import jax.numpy as jnp

        def step(x, top_k, causal):
            if top_k is not None:
                x = x + top_k
            if causal:
                x = x * 2
            n = x.shape[0]
            if n > 4:
                x = x[:4]
            return jnp.sum(x)

        f = jax.jit(step)
        """
    )
    fs = graftcheck.check_trace(mi)
    assert not fs, [f.render() for f in fs]


def test_scan_body_is_traced():
    mi = _mi(
        """
        import jax
        import jax.numpy as jnp

        def outer(xs):
            def body(carry, x):
                s = jnp.add(carry, x)
                if s > 0:  # flagged: scan bodies trace too
                    pass
                return s, s
            return jax.lax.scan(body, 0.0, xs)
        """
    )
    fs = graftcheck.check_trace(mi)
    assert any(f.rule == "tracer-control-flow" for f in fs), _rules(fs)


# ---------------------------------------------------------------- locks


LOCK_FIXTURE = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0  # guarded_by: _lock
            self._d = {}  # guarded_by: loop [writes]

        def bad_lock(self):
            self._n += 1

        def good_lock(self):
            with self._lock:
                self._n += 1

        def helper(self):  # graftcheck: holds(_lock)
            self._n += 1

        def loop_write(self):  # graftcheck: runs-on(loop)
            self._d["k"] = 1

        def bad_domain_write(self):
            self._d["k"] = 1

        def torn_read_ok(self):
            return dict(self._d)
"""


def test_lock_discipline_fixture():
    mods = {"fixture.py": _mi(LOCK_FIXTURE)}
    fs = graftcheck.check_locks(mods)
    by_line = {(f.line, f.rule) for f in fs}
    src = textwrap.dedent(LOCK_FIXTURE).splitlines()
    bad_lock_line = 1 + next(
        i for i, l in enumerate(src) if "def bad_lock" in l
    ) + 1
    bad_dom_line = 1 + next(
        i for i, l in enumerate(src) if "def bad_domain_write" in l
    ) + 1
    assert (bad_lock_line, "unguarded-write") in by_line, sorted(by_line)
    assert (bad_dom_line, "unguarded-write") in by_line, sorted(by_line)
    # exactly the two seeded violations: the locked/annotated/read
    # accesses all pass
    assert len(fs) == 2, [f.render() for f in fs]


def test_foreign_receiver_needs_matching_lock():
    mods = {"fixture.py": _mi(
        """
        import threading

        class Index:
            def __init__(self):
                self._lock = threading.Lock()
                self._pins = 0  # guarded_by: _lock

        class Lease:
            def ok(self, index):
                with index._lock:
                    index._pins -= 1

            def bad(self, index):
                index._pins -= 1
        """
    )}
    fs = graftcheck.check_locks(mods)
    assert len(fs) == 1 and fs[0].rule == "unguarded-write", (
        [f.render() for f in fs]
    )


def test_wrong_lock_is_not_accepted():
    # a same-named but DIFFERENT lock must not certify the access
    mods = {"fixture.py": _mi(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0  # guarded_by: _lock

            def bad(self, _lock):
                with _lock:      # caller-supplied, not self._lock
                    self._n += 1
        """
    )}
    fs = graftcheck.check_locks(mods)
    assert len(fs) == 1 and fs[0].rule == "unguarded-write", (
        [f.render() for f in fs]
    )


def test_suppression_covers_multiline_statement():
    mi = _mi(
        """
        class C:
            def f(self):
                self._stats[
                    "k"
                ] += 1  # graftcheck: ignore[unguarded-write] -- reason
        """
    )
    # the finding anchors to the Attribute's line (the statement
    # start); the comment sits on the last physical line — both must
    # be covered
    assert "unguarded-write" in mi.suppress.get(4, set()), mi.suppress
    assert "unguarded-write" in mi.suppress.get(6, set()), mi.suppress


def test_suppression_parsing():
    mi = _mi(
        """
        x = 1  # graftcheck: ignore[unguarded-write] -- documented torn read
        y = 2  # graftcheck: ignore[metric-drift]
        """
    )
    assert mi.suppress.get(2) == {"unguarded-write"}
    assert mi.bad_suppressions == [3]  # no reason given


# ---------------------------------------------------------------- drift


def _write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(content))


def test_drift_fixture_project(tmp_path):
    root = str(tmp_path)
    _write(root, "mlcomp_tpu/mod.py", """
        import os
        from mlcomp_tpu.utils.faults import inject

        def f():
            inject("dead.point")
            return os.environ.get("MLCOMP_TPU_UNDOCUMENTED")
        """)
    _write(root, "mlcomp_tpu/engine.py", """
        def collect(m):
            m.counter("mlcomp_engine_real_total", "help")
            m.counter("mlcomp_engine_unlisted_total", "help")
        """)
    _write(root, "tools/obs_check.py", """
        DOCUMENTED_SERVE_METRICS = [
            "mlcomp_engine_real_total",
        ]
        """)
    _write(root, "docs/serving.md", """
        ## Environment variables

        | variable | read in | meaning |
        |---|---|---|
        | `MLCOMP_TPU_STALE_ROW` | nowhere | stale |
        """)
    _write(root, "docs/observability.md", """
        ## Metrics catalog — serve daemon

        | name | type | meaning |
        |---|---|---|
        | `mlcomp_engine_real_total` | counter | present in code |
        | `mlcomp_engine_stale_total` | counter | registered nowhere |
        """)
    _write(root, "README.md", "run with `--no-such-flag` for fun\n")
    fs = graftcheck.check_drift(root)
    msgs = "\n".join(f.render() for f in fs)
    # env: undocumented read + stale row
    assert "MLCOMP_TPU_UNDOCUMENTED" in msgs, msgs
    assert "MLCOMP_TPU_STALE_ROW" in msgs, msgs
    # metrics: registered-but-undocumented + documented-but-unregistered
    # + documented-but-unenforced (obs_check list)
    assert "mlcomp_engine_unlisted_total" in msgs, msgs
    assert "mlcomp_engine_stale_total" in msgs, msgs
    # fault point never armed anywhere
    assert "dead.point" in msgs, msgs
    # doc references a flag no add_argument defines
    assert "--no-such-flag" in msgs, msgs


@pytest.mark.parametrize("group, reported", [("toy", False), ("ghost", True)])
def test_a_layers_counts_are_conditional_by_the_layers_table(
        tmp_path, group, reported):
    """What a layer counts is exempt from obs_check's list by the table
    its model file declares, and by nothing else: a documented metric
    of a group no table declares is reported."""
    root = str(tmp_path)
    _write(root, "mlcomp_tpu/models/toy.py", """
        from mlcomp_tpu.models.counts import count_group

        COUNTS = count_group("toy", (
            ("things", "Things a call counted, "
             "summed over layers"),
            ("layer_calls", "Toy-layer calls"),
        ))
        """)
    _write(root, "mlcomp_tpu/engine.py", """
        def collect(m, entries):
            for group, name, what in entries:
                m.counter(f"mlcomp_engine_{group}_{name}_total", what)
        """)
    _write(root, "tools/obs_check.py", "DOCUMENTED_SERVE_METRICS = []\n")
    _write(root, "docs/serving.md",
           "## Environment variables\n\n| variable |\n|---|\n")
    _write(root, "docs/observability.md", f"""
        ## Metrics catalog — serve daemon

        | name | type | meaning |
        |---|---|---|
        | `mlcomp_engine_{group}_things_total` | counter | a layer's count |
        """)
    assert graftcheck.collect_count_metrics(graftcheck.load_modules(
        root, ["mlcomp_tpu/models/toy.py"]
    )) == {"mlcomp_engine_toy_things_total",
           "mlcomp_engine_toy_layer_calls_total"}
    fs = [f for f in graftcheck.check_drift(root)
          if f.rule == "metric-drift"]
    if reported:
        assert len(fs) == 1 and "count_group" in fs[0].message, fs
        assert "mlcomp_engine_ghost_things_total" in fs[0].message
    else:
        assert not fs, [f.render() for f in fs]


def test_metric_docs_parser_handles_brace_expansion():
    docs = textwrap.dedent("""
        ## Metrics catalog — serve daemon

        | name | type | meaning |
        |---|---|---|
        | `mlcomp_prefix_cache_{hits,misses}_total` | counter | x |
        | `mlcomp_serving_requests_rejected_total{reason=…}` | counter | x |
        """)
    names = graftcheck.parse_metric_docs(docs)
    assert names == {
        "mlcomp_prefix_cache_hits_total",
        "mlcomp_prefix_cache_misses_total",
        "mlcomp_serving_requests_rejected_total",
    }, names


# ------------------------------------------------- the repo, end to end


def test_repo_is_clean_and_fast():
    """The acceptance gate: zero unsuppressed findings on the real
    repo, all four passes.  The seconds are printed, not asserted: they
    are the machine's as much as the tool's."""
    t0 = time.monotonic()
    findings = graftcheck.run_passes(graftcheck.REPO)
    elapsed = time.monotonic() - t0
    print(f"graftcheck took {elapsed:.1f}s")
    assert not findings, "\n".join(f.render() for f in findings)


def test_cli_entrypoint(tmp_path):
    # a tiny clean project keeps the CLI round trip off the full-repo
    # analysis (test_repo_is_clean_and_fast already pays that once)
    root = str(tmp_path)
    _write(root, "mlcomp_tpu/mod.py", "x = 1\n")
    _write(root, "docs/serving.md",
           "## Environment variables\n\n| variable |\n|---|\n")
    _write(root, "docs/observability.md",
           "## Metrics catalog — serve daemon\n\n| name |\n|---|\n")
    assert graftcheck.main(["--root", root]) == 0
    assert graftcheck.main(["--root", root, "--json"]) == 0
    assert graftcheck.main(
        ["--root", root, "--rules", "use-after-donate,host-sync"]
    ) == 0
    assert graftcheck.main(["--rules", "no-such-rule"]) == 2
    assert graftcheck.main(["--list-rules"]) == 0
