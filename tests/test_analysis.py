"""jaxlint analyzer tests: one positive and one negative fixture per
rule, jit-boundary inference against a miniature of the lazy
``__getattr__`` builder pattern, baseline add/expire round-trip,
suppression comments, CLI exit codes, and the tracecheck runtime shim.
"""
import ast
import json
import textwrap

import pytest

from deeplearning4j_tpu.analysis import boundaries
from deeplearning4j_tpu.analysis.baseline import Baseline
from deeplearning4j_tpu.analysis.engine import analyze_source
from deeplearning4j_tpu.analysis.rules import RULES, RULES_BY_ID


def findings_for(src, rule_id=None):
    out = analyze_source(textwrap.dedent(src), path="fixture.py")
    if rule_id is None:
        return out
    return [f for f in out if f.rule == rule_id]


def ids_of(src):
    return {f.rule for f in findings_for(src)}


# ---------------------------------------------------------------------------
# rule registry basics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_at_least_ten_rules(self):
        assert len(RULES) >= 10

    def test_every_rule_has_metadata(self):
        for r in RULES:
            assert r.id.startswith("JL") and len(r.id) == 5
            assert r.severity in ("error", "warning", "info")
            assert r.hint and r.title

    def test_ids_unique(self):
        assert len(RULES_BY_ID) == len(RULES)


# ---------------------------------------------------------------------------
# JL0xx trace purity
# ---------------------------------------------------------------------------

class TestPurityRules:
    def test_jl001_positive(self):
        src = """
            import jax
            import numpy as np
            @jax.jit
            def f(x):
                noise = np.random.normal(size=3)
                return x + noise
        """
        assert findings_for(src, "JL001")

    def test_jl001_negative_outside_jit(self):
        src = """
            import numpy as np
            def sample(x):
                return x + np.random.normal(size=3)
        """
        assert not findings_for(src, "JL001")

    def test_jl002_positive(self):
        src = """
            import jax
            import time as _time
            @jax.jit
            def step(x):
                t0 = _time.perf_counter()
                return x * t0
        """
        assert findings_for(src, "JL002")

    def test_jl002_negative_host_side(self):
        src = """
            import time
            def step_timer():
                return time.perf_counter()
        """
        assert not findings_for(src, "JL002")

    def test_jl003_positive_print_and_logger(self):
        src = """
            import jax
            import logging
            log = logging.getLogger(__name__)
            @jax.jit
            def f(x):
                print("tracing", x)
                log.info("x=%s", x)
                return x
        """
        hits = findings_for(src, "JL003")
        assert len(hits) == 2

    def test_jl003_negative(self):
        src = """
            def report(x):
                print("done", x)
        """
        assert not findings_for(src, "JL003")

    def test_jl004_positive_self_write(self):
        src = """
            import jax
            class M:
                def build(self):
                    self._step = jax.jit(self._impl)
                def _impl(self, x):
                    self.calls = 1
                    return x
        """
        assert findings_for(src, "JL004")

    def test_jl004_negative_untraced_method(self):
        src = """
            class M:
                def bump(self):
                    self.calls = 1
        """
        assert not findings_for(src, "JL004")

    def test_jl005_positive(self):
        src = """
            import jax
            @jax.jit
            def f(x, flag):
                if flag:
                    return x
                return -x
        """
        assert findings_for(src, "JL005")

    def test_jl005_negative_static_argnames(self):
        src = """
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("flag",))
            def f(x, flag):
                if flag:
                    return x
                return -x
        """
        assert not findings_for(src, "JL005")

    def test_jl005_negative_none_check_in_boolop(self):
        src = """
            import jax
            @jax.jit
            def f(x, rng):
                if x.ndim and rng is not None:
                    return x
                return -x
        """
        assert not findings_for(src, "JL005")


# ---------------------------------------------------------------------------
# JL1xx hidden host syncs
# ---------------------------------------------------------------------------

class TestSyncRules:
    def test_jl101_positive(self):
        src = """
            def fit(model, data):
                total = 0.0
                for batch in data:
                    total += float(model.score_value)
                return total
        """
        assert findings_for(src, "JL101")

    def test_jl101_negative_index_coercion(self):
        src = """
            def fit(model, data, epochs):
                n = int(epochs)
                for iteration in data:
                    i = int(iteration)
                return n
        """
        assert not findings_for(src, "JL101")

    def test_jl101_callback_body_is_hot(self):
        src = """
            def iteration_done(model, iteration):
                return float(model.score_value)
        """
        assert findings_for(src, "JL101")

    def test_jl102_positive(self):
        src = """
            def train(batches):
                out = []
                for b in batches:
                    out.append(b.loss.item())
                return out
        """
        assert findings_for(src, "JL102")

    def test_jl102_negative_cold_path(self):
        src = """
            def summarize(arr):
                return arr.item()
        """
        assert not findings_for(src, "JL102")

    def test_jl103_positive_in_loop(self):
        src = """
            import numpy as np
            def fit(model, data):
                for batch in data:
                    host = np.asarray(batch)
                return host
        """
        assert findings_for(src, "JL103")

    def test_jl103_negative_entry_conversion(self):
        src = """
            import numpy as np
            def fit(model, data):
                data = np.asarray(data)
                return data
        """
        assert not findings_for(src, "JL103")


# ---------------------------------------------------------------------------
# JL2xx recompile hazards
# ---------------------------------------------------------------------------

class TestRecompileRules:
    def test_jl201_positive(self):
        src = """
            import jax
            def g(sizes, x):
                return x
            step = jax.jit(g, static_argnums=(0,))
            def run(x):
                return step([1, 2], x)
        """
        assert findings_for(src, "JL201")

    def test_jl201_negative_hashable(self):
        src = """
            import jax
            def g(sizes, x):
                return x
            step = jax.jit(g, static_argnums=(0,))
            def run(x):
                return step((1, 2), x)
        """
        assert not findings_for(src, "JL201")

    def test_jl202_positive(self):
        src = """
            import jax
            import numpy as np
            WEIGHTS = np.ones(4)
            @jax.jit
            def f(x):
                return x * WEIGHTS
        """
        assert findings_for(src, "JL202")

    def test_jl202_negative_passed_as_argument(self):
        src = """
            import jax
            import numpy as np
            WEIGHTS = np.ones(4)
            @jax.jit
            def f(x, weights):
                return x * weights
            def call(x):
                return f(x, WEIGHTS)
        """
        assert not findings_for(src, "JL202")

    def test_jl203_positive(self):
        src = """
            def train_step(x, log):
                for _ in range(2):
                    log(f"input shape={x.shape}")
                return x
        """
        assert findings_for(src, "JL203")

    def test_jl203_negative_cold_function(self):
        src = """
            def describe(x):
                return f"shape={x.shape}"
        """
        assert not findings_for(src, "JL203")


# ---------------------------------------------------------------------------
# JL301 donation
# ---------------------------------------------------------------------------

class TestDonationRule:
    def test_jl301_positive(self):
        src = """
            import jax
            class M:
                def build(self):
                    self._step = jax.jit(self._impl, donate_argnums=(0,))
                def run(self, x):
                    out = self._step(self.params, x)
                    return self.params
        """
        assert findings_for(src, "JL301")

    def test_jl301_negative_reassigned_first(self):
        src = """
            import jax
            class M:
                def build(self):
                    self._step = jax.jit(self._impl, donate_argnums=(0,))
                def run(self, x):
                    out = self._step(self.params, x)
                    self.params = out
                    return self.params
        """
        assert not findings_for(src, "JL301")

    def test_jl301_negative_multiline_call_args(self):
        # the donating call's own (continuation-line) argument loads must
        # not count as reads-after-donate
        src = """
            import jax
            class M:
                def build(self):
                    self._step = jax.jit(self._impl, donate_argnums=(0, 1))
                def run(self, x):
                    out = self._step(
                        self.params,
                        self.opt_state, x)
                    (self.params, self.opt_state) = out
                    return out
        """
        assert not findings_for(src, "JL301")

    def test_jl301_negative_across_exclusive_branches(self):
        src = """
            import jax
            class M:
                def build(self):
                    self._step = jax.jit(self._impl, donate_argnums=(0,))
                def run(self, x, fancy):
                    if fancy:
                        out = self._step(self.params, x)
                        self._commit(out)
                        return out
                    out = self._step(self.params, x)
                    self._commit(out)
                    return out
        """
        assert not findings_for(src, "JL301")


# ---------------------------------------------------------------------------
# JL401 lock discipline
# ---------------------------------------------------------------------------

class TestLockRule:
    def test_jl401_positive_unguarded(self):
        src = """
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    self.count += 1
                def snapshot(self):
                    return self.count
        """
        assert findings_for(src, "JL401")

    def test_jl401_negative_guarded(self):
        src = """
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    with self._lock:
                        self.count += 1
                def snapshot(self):
                    return self.count
        """
        assert not findings_for(src, "JL401")

    def test_jl401_inconsistent_guards_flagged(self):
        src = """
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._other_lock = threading.Lock()
                    self.count = 0
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    with self._lock:
                        self.count += 1
                def bump(self):
                    with self._other_lock:
                        self.count += 1
        """
        assert findings_for(src, "JL401")

    def test_jl401_atomic_annotation(self):
        src = """
            import threading
            class Worker:
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    self.done = True  # jaxlint: atomic
                def poll(self):
                    return self.done
        """
        assert not findings_for(src, "JL401")


# ---------------------------------------------------------------------------
# JL402 lock-order cycles
# ---------------------------------------------------------------------------

LOCK_CYCLE_SRC = """
    import threading
    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
        def ab(self):
            with self._a:
                with self._b:
                    pass
        def ba(self):
            with self._b:
                with self._a:
                    pass
"""


class TestLockOrderRule:
    def test_jl402_positive_cycle(self):
        found = findings_for(LOCK_CYCLE_SRC, "JL402")
        assert found
        assert "deadlock" in found[0].message

    def test_jl402_negative_consistent_order(self):
        src = """
            import threading
            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def ab(self):
                    with self._a:
                        with self._b:
                            pass
                def ab_again(self):
                    with self._a:
                        with self._b:
                            pass
        """
        assert not findings_for(src, "JL402")

    def test_jl402_transitive_callee_cycle(self):
        # inversion only visible through the one-level callee expansion
        src = """
            import threading
            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def _take_b(self):
                    with self._b:
                        pass
                def ab(self):
                    with self._a:
                        self._take_b()
                def _take_a(self):
                    with self._a:
                        pass
                def ba(self):
                    with self._b:
                        self._take_a()
        """
        assert findings_for(src, "JL402")

    def test_lock_edges_from_source_exposes_graph(self):
        from deeplearning4j_tpu.analysis import rules
        edges = rules.lock_edges_from_source(textwrap.dedent(LOCK_CYCLE_SRC))
        assert ("Pair._a", "Pair._b") in edges
        assert ("Pair._b", "Pair._a") in edges


# ---------------------------------------------------------------------------
# JL403 blocking under a held lock
# ---------------------------------------------------------------------------

class TestBlockingUnderLockRule:
    def test_jl403_positive_sleep_under_lock(self):
        src = """
            import threading
            import time
            class Srv:
                def __init__(self):
                    self._lock = threading.Lock()
                def pause(self):
                    with self._lock:
                        time.sleep(1.0)
        """
        found = findings_for(src, "JL403")
        assert found
        assert "Srv._lock" in found[0].message

    def test_jl403_positive_queue_get_and_forward(self):
        src = """
            import threading
            class Srv:
                def __init__(self):
                    self._lock = threading.Lock()
                def drain(self):
                    with self._lock:
                        item = self._queue.get()
                def run(self, x):
                    with self._lock:
                        return self.model.output(x)
        """
        assert len(findings_for(src, "JL403")) == 2

    def test_jl403_negative_outside_lock(self):
        src = """
            import threading
            import time
            class Srv:
                def __init__(self):
                    self._lock = threading.Lock()
                def pause(self):
                    with self._lock:
                        flag = True
                    time.sleep(1.0)
                def poll(self):
                    item = self._queue.get(timeout=0.1)
        """
        assert not findings_for(src, "JL403")

    def test_jl403_wait_on_own_condition_ok(self):
        # cv.wait() releases the lock it guards — not a blocking hazard
        src = """
            import threading
            class Srv:
                def __init__(self):
                    self._cv = threading.Condition()
                def park(self):
                    with self._cv:
                        self._cv.wait(timeout=1.0)
        """
        assert not findings_for(src, "JL403")


# ---------------------------------------------------------------------------
# JL404 field-level atomicity
# ---------------------------------------------------------------------------

DROPPED_RACE_SRC = """
    import threading
    class Stats:
        def __init__(self):
            self._lock = threading.Lock()
            self.dropped = 0
        def reset(self):
            with self._lock:
                self.dropped = 0
        def shed(self):
            self.dropped += 1
"""


class TestFieldAtomicityRule:
    def test_jl404_positive_unguarded_rmw(self):
        found = findings_for(DROPPED_RACE_SRC, "JL404")
        assert found
        assert "dropped" in found[0].message
        assert "lost-update" in found[0].message

    def test_jl404_negative_all_guarded(self):
        src = """
            import threading
            class Stats:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.dropped = 0
                def reset(self):
                    with self._lock:
                        self.dropped = 0
                def shed(self):
                    with self._lock:
                        self.dropped += 1
        """
        assert not findings_for(src, "JL404")

    def test_jl404_locked_suffix_exempt(self):
        # *_locked methods run with the caller's lock held by convention
        src = """
            import threading
            class Stats:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.dropped = 0
                def reset(self):
                    with self._lock:
                        self.dropped = 0
                def _shed_locked(self):
                    self.dropped += 1
        """
        assert not findings_for(src, "JL404")

    def test_jl404_atomic_annotation(self):
        src = """
            import threading
            class Stats:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.dropped = 0
                def reset(self):
                    with self._lock:
                        self.dropped = 0
                def shed(self):
                    self.dropped += 1  # jaxlint: atomic
        """
        assert not findings_for(src, "JL404")

    def test_jl404_check_then_act_read(self):
        src = """
            import threading
            class Srv:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._shutdown = False
                def close(self):
                    with self._lock:
                        self._shutdown = True
                def submit(self, x):
                    if self._shutdown:
                        raise RuntimeError("closed")
        """
        found = findings_for(src, "JL404")
        assert found
        assert "check-then-act" in found[0].message


# ---------------------------------------------------------------------------
# JL501 typed route errors
# ---------------------------------------------------------------------------

class TestRouteTypedErrorRule:
    def test_jl501_positive_untyped_raise(self):
        src = """
            def _predict_route(self, name, payload):
                if not payload:
                    raise RuntimeError("bad payload")
                return 200
        """
        found = findings_for(src, "JL501")
        assert found
        assert "RuntimeError" in found[0].message

    def test_jl501_positive_unprotected_raising_call(self):
        src = """
            def _predict_route(self, name, payload):
                out = self.engine.predict(payload)
                return out
        """
        assert findings_for(src, "JL501")

    def test_jl501_negative_taxonomy_and_try(self):
        src = """
            from deeplearning4j_tpu.parallel.inference import QueueFullError
            def _predict_route(self, name, payload):
                if not payload:
                    raise QueueFullError("shed")
                try:
                    out = self.engine.predict(payload)
                except QueueFullError:
                    return 429
                return out
        """
        assert not findings_for(src, "JL501")

    def test_jl501_negative_non_route_function(self):
        src = """
            def helper(self, payload):
                raise RuntimeError("not a route")
        """
        assert not findings_for(src, "JL501")


# ---------------------------------------------------------------------------
# JL502 metrics discipline
# ---------------------------------------------------------------------------

class TestMetricsDisciplineRule:
    def test_jl502_positive_hot_construction(self):
        src = """
            from deeplearning4j_tpu.optimize.metrics import registry
            def fit_batch(self, x):
                registry().counter("steps_total", "steps").inc()
        """
        found = findings_for(src, "JL502")
        assert found
        assert "steps_total" in found[0].message

    def test_jl502_negative_register_fn(self):
        src = """
            from deeplearning4j_tpu.optimize.metrics import registry
            def register_metrics():
                registry().counter("steps_total", "steps")
            def fit_batch(self, x):
                self._steps.labels(model="m").inc()
        """
        assert not findings_for(src, "JL502")

    def test_jl502_positive_unbounded_label(self):
        src = """
            def handle(self, fam, req):
                fam.labels(request_id=req.rid).inc()
        """
        found = findings_for(src, "JL502")
        assert found
        assert "request_id" in found[0].message

    def test_jl502_positive_unbounded_value_call(self):
        src = """
            import uuid
            def handle(self, fam):
                fam.labels(run=uuid.uuid4()).inc()
        """
        assert findings_for(src, "JL502")

    def test_jl502_negative_bounded_labels(self):
        src = """
            def handle(self, fam, req):
                fam.labels(model=req.model, outcome="ok").inc()
        """
        assert not findings_for(src, "JL502")

    def _serving_tree(self, tmp_path, family):
        """A miniature checkout: deeplearning4j_tpu/serving/mod.py using
        ``family``, with only 'registered_total' pre-registered."""
        pkg = tmp_path / "deeplearning4j_tpu"
        serving = pkg / "serving"
        serving.mkdir(parents=True)
        (pkg / "metrics.py").write_text(textwrap.dedent("""
            def register_serving_metrics(reg):
                reg.counter("registered_total", "help")
        """))
        mod = serving / "mod.py"
        mod.write_text(textwrap.dedent(f"""
            def handle(self, reg):
                reg.counter("{family}", "help").inc()
        """))
        return str(mod)

    def test_jl502_positive_unregistered_serving_family(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._serving_tree(tmp_path, "unregistered_total")
        found = [f for f in analyze_paths([path]) if f.rule == "JL502"]
        assert found
        assert "unregistered_total" in found[0].message

    def test_jl502_negative_preregistered_serving_family(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._serving_tree(tmp_path, "registered_total")
        assert not [f for f in analyze_paths([path]) if f.rule == "JL502"]


# ---------------------------------------------------------------------------
# JL503 fault-point coverage
# ---------------------------------------------------------------------------

class TestFaultCoverageRule:
    def _fault_tree(self, tmp_path, *, tested, documented):
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        mod = pkg / "mod.py"
        mod.write_text(textwrap.dedent("""
            from .utils import faults
            def run():
                faults.fire("serve.forward")
        """))
        tests = tmp_path / "tests"
        tests.mkdir()
        (tests / "test_mod.py").write_text(
            "POINT = 'serve.forward'\n" if tested else "POINT = 'other'\n")
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "faults.md").write_text(
            "| serve.forward | drops a forward |\n" if documented
            else "| nothing |\n")
        return str(mod)

    def test_jl503_positive_untested_point(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._fault_tree(tmp_path, tested=False, documented=True)
        found = [f for f in analyze_paths([path]) if f.rule == "JL503"]
        assert found
        assert "serve.forward" in found[0].message
        assert "test" in found[0].message

    def test_jl503_positive_undocumented_point(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._fault_tree(tmp_path, tested=True, documented=False)
        found = [f for f in analyze_paths([path]) if f.rule == "JL503"]
        assert found
        assert "docs" in found[0].message

    def test_jl503_negative_covered_point(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._fault_tree(tmp_path, tested=True, documented=True)
        assert not [f for f in analyze_paths([path]) if f.rule == "JL503"]

    def test_jl503_inline_disable(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._fault_tree(tmp_path, tested=False, documented=True)
        with open(path, "w") as fh:
            fh.write(textwrap.dedent("""
                from .utils import faults
                def run():
                    faults.fire("serve.forward")  # jaxlint: disable=JL503
            """))
        assert not [f for f in analyze_paths([path]) if f.rule == "JL503"]

    def test_jl503_baseline_round_trip(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._fault_tree(tmp_path, tested=False, documented=False)
        findings = [f for f in analyze_paths([path]) if f.rule == "JL503"]
        assert findings
        bl = Baseline()
        bl.record(findings, default_justification="hook lands next PR")
        result = bl.match([f for f in analyze_paths([path])
                           if f.rule == "JL503"])
        assert not result.new

    def test_jl503_env_var_form_counts_as_tested(self, tmp_path):
        from deeplearning4j_tpu.analysis.engine import analyze_paths
        path = self._fault_tree(tmp_path, tested=False, documented=True)
        import os
        tests_dir = os.path.join(str(tmp_path), "tests")
        with open(os.path.join(tests_dir, "test_env.py"), "w") as fh:
            fh.write("ENV = 'DL4JTPU_FAULT_SERVE_FORWARD'\n")
        # corpus is cached per repo root; new file → bust the cache
        from deeplearning4j_tpu.analysis import rules
        rules._CORPUS_CACHE.clear()
        assert not [f for f in analyze_paths([path]) if f.rule == "JL503"]


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

class TestSuppression:
    def test_disable_single_rule(self):
        src = """
            def fit(model, data):
                for b in data:
                    s = float(model.score_value)  # jaxlint: disable=JL101
                return s
        """
        assert not findings_for(src, "JL101")

    def test_disable_all(self):
        src = """
            def fit(model, data):
                for b in data:
                    s = float(model.score_value)  # jaxlint: disable=all
                return s
        """
        assert not findings_for(src)

    def test_disable_other_rule_does_not_mask(self):
        src = """
            def fit(model, data):
                for b in data:
                    s = float(model.score_value)  # jaxlint: disable=JL999
                return s
        """
        assert findings_for(src, "JL101")

    def test_disable_each_new_rule(self):
        """Every JL4xx/JL5xx rule honours an inline disable at its
        reporting site (the suppression half of each round-trip)."""
        cases = {
            "JL402": """
                import threading
                class Pair:
                    def __init__(self):
                        self._a = threading.Lock()
                        self._b = threading.Lock()
                    def ab(self):
                        with self._a:
                            with self._b:  # jaxlint: disable=JL402
                                pass
                    def ba(self):
                        with self._b:
                            with self._a:  # jaxlint: disable=JL402
                                pass
            """,
            "JL403": """
                import threading
                import time
                class Srv:
                    def __init__(self):
                        self._lock = threading.Lock()
                    def pause(self):
                        with self._lock:
                            time.sleep(1.0)  # jaxlint: disable=JL403
            """,
            "JL404": """
                import threading
                class Stats:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.dropped = 0
                    def reset(self):
                        with self._lock:
                            self.dropped = 0
                    def shed(self):
                        self.dropped += 1  # jaxlint: disable=JL404
            """,
            "JL501": """
                def _predict_route(self, name, payload):
                    raise RuntimeError("x")  # jaxlint: disable=JL501
            """,
            "JL502": """
                from deeplearning4j_tpu.optimize.metrics import registry
                def fit_batch(self, x):
                    registry().counter("t", "h").inc()  # jaxlint: disable=JL502
            """,
        }
        for rule_id, src in cases.items():
            assert not findings_for(src, rule_id), rule_id
            # and the fixture genuinely fires without the comment
            naked = src.replace(f"  # jaxlint: disable={rule_id}", "")
            assert findings_for(naked, rule_id), rule_id

    def test_baseline_round_trip_each_new_rule(self, tmp_path):
        """Every new rule's findings baseline away with a justification
        and come back expired once fixed (the baseline half)."""
        firing = {
            "JL402": LOCK_CYCLE_SRC,
            "JL404": DROPPED_RACE_SRC,
            "JL501": """
                def _predict_route(self, name, payload):
                    raise RuntimeError("x")
            """,
        }
        for rule_id, src in firing.items():
            findings = findings_for(src, rule_id)
            assert findings, rule_id
            bl = Baseline()
            bl.record(findings, default_justification="known, tracked")
            result = bl.match(findings_for(src, rule_id))
            assert not result.new, rule_id
            assert result.known[0].justification == "known, tracked"
            fixed = bl.match([])
            assert len(fixed.expired) == len(findings), rule_id


# ---------------------------------------------------------------------------
# jit-boundary inference
# ---------------------------------------------------------------------------

LAZY_GETATTR_SRC = textwrap.dedent("""
    import jax
    from deeplearning4j_tpu.optimize import compile_cache as cc

    def train_step(params, opt_state, rng, batch, flag):
        return params, opt_state

    def helper(params):
        return params

    class Net:
        _TRAIN_JIT_ATTRS = ("_train_step_fn",)

        def __getattr__(self, name):
            if name in type(self)._TRAIN_JIT_ATTRS:
                self._build_training_jits()
                return object.__getattribute__(self, name)
            raise AttributeError(name)

        def _build_training_jits(self):
            self._train_step_fn = cc.PrecompiledDispatch(
                jax.jit(train_step, donate_argnums=(0, 1),
                        static_argnums=(4,)), tag="train_step")
""")


class TestBoundaries:
    def test_lazy_getattr_jit_builder(self):
        tree = ast.parse(LAZY_GETATTR_SRC)
        info = boundaries.infer(tree)
        root_names = {getattr(n, "name", "") for n in info.roots}
        assert "train_step" in root_names
        assert len(info.assignments) == 1
        asg = info.assignments[0]
        assert asg.target_name == "_train_step_fn"
        assert asg.is_self_attr
        assert asg.fn_name == "train_step"
        assert asg.donate_argnums == (0, 1)
        assert asg.static_argnums == (4,)

    def test_transitive_callee_reachable(self):
        src = textwrap.dedent("""
            import jax
            def inner(x):
                return x
            @jax.jit
            def outer(x):
                return inner(x)
        """)
        info = boundaries.infer(ast.parse(src))
        names = {getattr(n, "name", "") for n in info.reachable}
        assert {"outer", "inner"} <= names

    def test_lambda_and_scan_body_are_roots(self):
        src = textwrap.dedent("""
            import jax
            def body(c, x):
                return c, x
            def run(xs):
                return jax.lax.scan(body, 0, xs)
            f = jax.jit(lambda x: x + 1)
        """)
        info = boundaries.infer(ast.parse(src))
        assert any(isinstance(n, ast.Lambda) for n in info.roots)
        names = {getattr(n, "name", "") for n in info.roots}
        assert "body" in names

    def test_alias_resolution(self):
        src = "from jax import numpy as jnp\nimport time as _time\n"
        aliases = boundaries.build_alias_map(ast.parse(src))
        assert aliases["jnp"] == "jax.numpy"
        assert aliases["_time"] == "time"

    def test_traced_dunder_declares_roots(self):
        # __traced__ marks functions jitted from ANOTHER file as roots
        src = textwrap.dedent("""
            __traced__ = ("kernel_entry",)
            def kernel_entry(x):
                return helper(x)
            def helper(x):
                return x
            def untouched(x):
                return x
        """)
        info = boundaries.infer(ast.parse(src))
        roots = {getattr(n, "name", "") for n in info.roots}
        reach = {getattr(n, "name", "") for n in info.reachable}
        assert roots == {"kernel_entry"}
        assert {"kernel_entry", "helper"} <= reach
        assert "untouched" not in reach

    def test_traced_dunder_ignores_unknown_names(self):
        src = '__traced__ = ("missing",)\ndef real(x):\n    return x\n'
        info = boundaries.infer(ast.parse(src))
        assert not info.roots

    @pytest.mark.parametrize("relpath,surface", [
        ("serving/decode.py", "_prefill_pure"),
        ("serving/decode.py", "_step_pure"),
        ("quantize/quantize.py", "dense_qforward"),
        ("ops/flash_attention.py", "paged_decode_attention"),
        ("ops/flash_attention.py", "prefill_attention"),
        ("ops/moe.py", "expert_ffn"),
    ])
    def test_post_pr5_jit_surface_reachable(self, relpath, surface):
        """Each post-PR-5 serving jit surface is seen by boundary
        inference, so the JL0xx/JL2xx purity rules cover its body."""
        import os
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(
            boundaries.__file__)))
        with open(os.path.join(pkg, relpath), "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        info = boundaries.infer(tree)
        names = {getattr(n, "name", "") for n in info.reachable}
        assert surface in names, (
            f"{relpath}:{surface} fell off the inferred jit boundary")


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

HOT_SYNC_SRC = """
    def fit(model, data):
        for b in data:
            s = float(model.score_value)
        return s
"""


class TestBaseline:
    def test_add_then_clean(self, tmp_path):
        findings = findings_for(HOT_SYNC_SRC)
        assert findings
        bl = Baseline()
        bl.record(findings, default_justification="known hot read")
        path = tmp_path / "baseline.json"
        bl.save(str(path))
        loaded = Baseline.load(str(path))
        result = loaded.match(findings_for(HOT_SYNC_SRC))
        assert not result.new
        assert len(result.known) == len(findings)
        assert result.known[0].justification == "known hot read"
        assert not result.expired

    def test_expired_entry_reported(self, tmp_path):
        findings = findings_for(HOT_SYNC_SRC)
        bl = Baseline()
        bl.record(findings, default_justification="known")
        # the offending line was fixed: nothing matches any more
        result = bl.match([])
        assert len(result.expired) == len(findings)
        assert not result.new

    def test_new_finding_not_masked(self):
        bl = Baseline()
        bl.record(findings_for(HOT_SYNC_SRC), default_justification="known")
        other = findings_for("""
            def train(batches):
                for b in batches:
                    v = b.loss.item()
                return v
        """)
        result = bl.match(other)
        assert result.new == other

    def test_multiset_semantics(self):
        findings = findings_for(HOT_SYNC_SRC)
        bl = Baseline()
        bl.record(findings, default_justification="known")
        doubled = findings + findings_for(HOT_SYNC_SRC)
        result = bl.match(doubled)
        # one budget entry per recorded finding; the duplicate is NEW
        assert len(result.new) == len(findings)

    def test_record_preserves_justifications(self):
        findings = findings_for(HOT_SYNC_SRC)
        bl = Baseline()
        bl.record(findings, default_justification="first pass")
        bl.record(findings_for(HOT_SYNC_SRC))
        assert bl.entries[0].justification == "first pass"

    def test_record_refuses_unjustified(self):
        findings = findings_for(HOT_SYNC_SRC)
        bl = Baseline()
        with pytest.raises(ValueError, match="justification"):
            bl.record(findings)
        assert not bl.entries     # refused write leaves nothing behind


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def _write(self, tmp_path, body):
        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent(body))
        return str(f)

    def test_exit_zero_on_clean_file(self, tmp_path):
        from deeplearning4j_tpu.analysis.cli import main
        path = self._write(tmp_path, "def add(a, b):\n    return a + b\n")
        assert main([path, "--no-baseline"]) == 0

    def test_exit_one_on_findings_then_zero_after_baseline(self, tmp_path,
                                                           capsys):
        from deeplearning4j_tpu.analysis.cli import main
        path = self._write(tmp_path, HOT_SYNC_SRC)
        bl = str(tmp_path / "baseline.json")
        assert main([path, "--baseline", bl]) == 1
        assert main([path, "--baseline", bl, "--write-baseline",
                     "--justify", "epoch-loop read, fenced next PR"]) == 0
        assert main([path, "--baseline", bl]) == 0
        out = json.loads((tmp_path / "baseline.json").read_text())
        assert out["entries"]
        assert all(e["justification"] for e in out["entries"])

    def test_write_baseline_refuses_unjustified(self, tmp_path, capsys):
        from deeplearning4j_tpu.analysis.cli import main
        path = self._write(tmp_path, HOT_SYNC_SRC)
        bl = str(tmp_path / "baseline.json")
        assert main([path, "--baseline", bl, "--write-baseline"]) == 2
        assert "justif" in capsys.readouterr().err
        assert not (tmp_path / "baseline.json").exists()

    def test_bare_rules_prints_catalog(self, capsys):
        from deeplearning4j_tpu.analysis.cli import main
        assert main(["--rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("JL402", "JL403", "JL404", "JL501", "JL502", "JL503"):
            assert rid in out
        assert "error" in out and "warning" in out

    def test_json_format(self, tmp_path, capsys):
        from deeplearning4j_tpu.analysis.cli import main
        path = self._write(tmp_path, HOT_SYNC_SRC)
        rc = main([path, "--no-baseline", "--format", "json"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert data["summary"]["new"] == len(data["new"]) >= 1

    def test_unknown_rule_is_usage_error(self, tmp_path):
        from deeplearning4j_tpu.analysis.cli import main
        path = self._write(tmp_path, "x = 1\n")
        assert main([path, "--rules", "JL999"]) == 2

    def test_syntax_error_reported_not_crash(self, tmp_path):
        from deeplearning4j_tpu.analysis.cli import main
        path = self._write(tmp_path, "def broken(:\n")
        assert main([path, "--no-baseline"]) == 1


# ---------------------------------------------------------------------------
# tracecheck runtime shim
# ---------------------------------------------------------------------------

class TestTracecheck:
    def test_float_on_jit_output_counts(self):
        import jax.numpy as jnp
        import jax
        from deeplearning4j_tpu.analysis import tracecheck as tc
        from deeplearning4j_tpu.optimize.metrics import registry
        tc.reset_counts()
        fam = registry().counter(
            tc.METRIC_NAME,
            "implicit device->host syncs observed by tracecheck")
        before = fam.value(site="t_float")
        out = tc.watch(jax.jit(lambda x: x * 2)(jnp.asarray(1.5)),
                       site="t_float")
        val = float(out)
        assert val == 3.0
        assert tc.sync_count("t_float") == 1
        assert fam.value(site="t_float") == before + 1

    def test_fenced_read_stays_flat(self):
        import jax.numpy as jnp
        import jax
        from deeplearning4j_tpu.analysis import tracecheck as tc
        tc.reset_counts()
        out = tc.watch(jax.jit(lambda x: x + 1)(jnp.asarray(1.0)),
                       site="t_fenced")
        host = tc.fenced_read(out)
        assert float(host) == 2.0
        assert tc.sync_count("t_fenced") == 0

    def test_item_and_asarray_count(self):
        import numpy as np
        import jax.numpy as jnp
        from deeplearning4j_tpu.analysis import tracecheck as tc
        tc.reset_counts()
        out = tc.watch(jnp.asarray([1.0, 2.0]), site="t_item")
        _ = np.asarray(out)
        _ = out.tolist()
        assert tc.sync_count("t_item") == 2

    def test_pytree_watch_and_passthrough(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.analysis import tracecheck as tc
        tc.reset_counts()
        tree = tc.watch({"w": jnp.ones(2), "n": 3}, site="t_tree")
        assert isinstance(tree["w"], tc.SyncSpy)
        assert tree["n"] == 3
        assert tuple(tree["w"].shape) == (2,)      # metadata: uncounted
        assert (tree["w"] + 1).shape == (2,)       # arithmetic: uncounted
        assert tc.sync_count("t_tree") == 0

    def test_wrap_decorator(self):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.analysis import tracecheck as tc
        tc.reset_counts()
        step = tc.wrap(jax.jit(lambda x: x * 3), site="t_wrap")
        out = step(jnp.asarray(2.0))
        assert isinstance(out, tc.SyncSpy)
        assert int(out) == 6
        assert tc.sync_count("t_wrap") == 1


# ---------------------------------------------------------------------------
# lockcheck runtime shim
# ---------------------------------------------------------------------------

class TestLockcheck:
    def _pair(self):
        """Two-lock class with an a->b and a b->a path (the classic
        inversion), built under recording() so its locks are proxies."""
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass

        return Pair

    def test_recording_observes_nesting(self):
        from deeplearning4j_tpu.analysis import lockcheck
        with lockcheck.recording():
            p = self._pair()()
            names = lockcheck.adopt(p, "Pair")
            p.ab()
        assert names == ["Pair._a", "Pair._b"]
        assert lockcheck.observed_edges() == {("Pair._a", "Pair._b"): 1}

    def test_recording_restores_factories(self):
        import threading
        from deeplearning4j_tpu.analysis import lockcheck
        real = threading.Lock
        with lockcheck.recording():
            assert threading.Lock is not real
        assert threading.Lock is real
        assert not isinstance(threading.Lock(), lockcheck.LockProxy)

    def test_rlock_reentry_is_not_an_edge(self):
        import threading
        from deeplearning4j_tpu.analysis import lockcheck
        with lockcheck.recording():
            r = threading.RLock()
            r.lockcheck_name = "R"
            with r:
                with r:
                    pass
        assert lockcheck.observed_edges() == {}

    def test_cross_check_confirms_static_graph(self):
        """The tentpole cross-check: runtime-observed ordering edges
        match JL402's static graph, and the inversion shows up as a
        cycle in both."""
        import inspect
        from deeplearning4j_tpu.analysis import lockcheck
        from deeplearning4j_tpu.analysis import rules
        Pair = None
        with lockcheck.recording():
            Pair = self._pair()
            p = Pair()
            lockcheck.adopt(p, "Pair")
            p.ab()
            p.ba()
        static = rules.lock_edges_from_source(
            textwrap.dedent(inspect.getsource(Pair)))
        report = lockcheck.cross_check(lockcheck.observed_edges(), static)
        assert report.confirmed == {("Pair._a", "Pair._b"),
                                    ("Pair._b", "Pair._a")}
        assert not report.unexplained and not report.unexercised
        assert report.cycles == [["Pair._a", "Pair._b"]]
        assert not report.ok()

    def test_cross_check_flags_unexplained_runtime_edge(self):
        from deeplearning4j_tpu.analysis import lockcheck
        observed = {("C.x", "C.y"): 3}
        report = lockcheck.cross_check(observed, {("C.y", "C.x"): None})
        assert report.unexplained == {("C.x", "C.y")}
        assert report.unexercised == {("C.y", "C.x")}
        # union graph has both directions: that IS the deadlock cycle
        assert report.cycles

    def test_cross_check_ignores_unadopted_noise(self):
        from deeplearning4j_tpu.analysis import lockcheck
        observed = {("lock-9", "lock-10"): 1}      # never adopt()ed
        report = lockcheck.cross_check(observed, {("C.x", "C.y"): None})
        assert not report.unexplained
        assert report.ok()

    def test_instrument_wraps_only_bare_locks(self):
        import threading
        from deeplearning4j_tpu.analysis import lockcheck

        class Mixed:
            def __init__(self):
                self._lock = threading.Lock()
                self._r = threading.RLock()
                self._cv = threading.Condition()
                self.count = 0

        m = Mixed()
        names = lockcheck.instrument(m, "Mixed")
        assert names == ["Mixed._lock", "Mixed._r"]
        assert isinstance(m._lock, lockcheck.LockProxy)
        assert not isinstance(m._cv, lockcheck.LockProxy)
        lockcheck.reset()
        with m._lock:
            with m._r:
                pass
        assert lockcheck.observed_edges() == {("Mixed._lock", "Mixed._r"): 1}

    def test_parallel_inference_runtime_vs_static(self):
        """Instrumenting a real serve+shutdown on ParallelInference and
        cross-checking against its static JL402 graph finds no cycles —
        the lock discipline holds live, not just on paper."""
        import os
        import numpy as np
        from deeplearning4j_tpu.analysis import lockcheck
        from deeplearning4j_tpu.analysis import rules
        from deeplearning4j_tpu.parallel import inference as inf

        class Toy:
            _initialized = True

            def output(self, x):
                return x

        srv = inf.ParallelInference(
            Toy(), inference_mode=inf.InferenceMode.SEQUENTIAL)
        names = lockcheck.instrument(srv)
        assert any(n.startswith("ParallelInference.") for n in names)
        lockcheck.reset()
        srv.output(np.ones((1, 2)))
        srv.shutdown()
        src_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(boundaries.__file__))),
            "parallel", "inference.py")
        with open(src_path, "r", encoding="utf-8") as fh:
            static = rules.lock_edges_from_source(fh.read())
        report = lockcheck.cross_check(lockcheck.observed_edges(), static)
        assert report.ok(), f"live deadlock ordering: {report.cycles}"


# ---------------------------------------------------------------------------
# regression tests for the defects the JL4xx/JL5xx triage surfaced —
# each analyzes the REAL shipped source, so reverting a fix re-fires
# the rule and fails the test
# ---------------------------------------------------------------------------

def _real_findings(relpath, rule_id):
    import os
    from deeplearning4j_tpu.analysis.engine import analyze_paths
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(
        boundaries.__file__)))
    return [f for f in analyze_paths([os.path.join(pkg, relpath)])
            if f.rule == rule_id]


class TestTriageDefectRegressions:
    def test_gateway_routes_raise_only_typed_errors(self):
        """serving/gateway.py defect: _predict_route/_generate_route
        looked up ``self.pool.get(name).version`` AFTER the protected
        try block, so a concurrent remove() between forward and lookup
        escaped as an untyped KeyError 500 instead of the typed 404.
        The fix moves the lookup inside the try; pre-fix source fires
        JL501 here."""
        assert not _real_findings("serving/gateway.py", "JL501")

    def test_inference_stats_counters_are_lock_guarded(self):
        """parallel/inference.py defect: total_forwards / total_shed /
        batch-failure counters were bumped bare from the collector
        thread AND caller threads — the exact 'dropped += 1' lost-update
        shape JL404 exists for. Fixed with _stats_lock; pre-fix source
        fires JL404 here."""
        assert not _real_findings("parallel/inference.py", "JL404")
        import inspect
        from deeplearning4j_tpu.parallel import inference as inf
        assert "_stats_lock" in inspect.getsource(inf.ParallelInference)

    def test_inference_shutdown_not_blocking_under_lock(self):
        """parallel/inference.py defect: shutdown() put the worker
        sentinel into a bounded queue while holding _enqueue_lock — a
        full queue wedged shutdown against every admitting caller. The
        sentinel put now happens outside the lock; pre-fix source fires
        JL403 here (the three deliberate forward-under-_lock swap-pause
        sites carry explicit inline suppressions instead)."""
        assert not _real_findings("parallel/inference.py", "JL403")

    def test_sequential_shutdown_with_full_queue_returns(self):
        """Behavioral half of the shutdown fix: shutting down must not
        deadlock and a post-shutdown submit gets the typed error."""
        import numpy as np
        import threading
        from deeplearning4j_tpu.parallel import inference as inf

        class Toy:
            _initialized = True

            def output(self, x):
                return x

        srv = inf.ParallelInference(
            Toy(), inference_mode=inf.InferenceMode.SEQUENTIAL)
        assert srv.output(np.ones((1, 2))).shape == (1, 2)
        t = threading.Thread(target=srv.shutdown)
        t.start()
        t.join(timeout=5.0)
        assert not t.is_alive(), "shutdown() wedged"
        with pytest.raises(inf.ServerClosedError):
            srv.output(np.ones((1, 2)))

    def test_cluster_health_snapshot_read(self):
        """parallel/cluster_health.py defect: _evaluate re-read
        self._started_at per member mid-loop while reconfigure() could
        rewrite it — a torn evaluation window. It now takes one
        snapshot; pre-fix source fires JL404 here."""
        assert not _real_findings("parallel/cluster_health.py", "JL404")

    def test_serving_families_preregistered_for_bench_once(self):
        """serving/gateway.py + model_pool.py defect: gateway latency /
        shed / tier families and pool swap/precision/queue-depth gauges
        were constructed lazily on first request, so a
        scrape before traffic missed them. register_metrics() now
        pre-registers every family; pre-fix source fires JL502 here."""
        assert not _real_findings("serving/gateway.py", "JL502")
        assert not _real_findings("serving/model_pool.py", "JL502")
        from deeplearning4j_tpu.serving import gateway
        assert callable(getattr(gateway, "register_metrics", None))


# ---------------------------------------------------------------------------
# the shipped tree stays clean (duplicated as a smoke test in
# tests/smoke_analysis.py for runtests.sh)
# ---------------------------------------------------------------------------

class TestShippedTree:
    def test_package_clean_against_committed_baseline(self):
        import os
        from deeplearning4j_tpu.analysis.cli import main
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(
            boundaries.__file__)))
        assert main([pkg]) == 0
