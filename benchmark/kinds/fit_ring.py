"""Traffic kind `fit_ring`: one `fit` call over an iterator that cycles a
ring of distinct seeded host batches until the window ends, so the fit
loop's own staging and prefetch run. Parameters (traffic file): `rows`,
`ring`, `follow_steps`.

Set-up builds the one net, drives it through its first `follow_steps`
steps with the window's own call and feed (ring batches 0, 1, 2: rows that
all differ), and hands that same object to the window. The reference
follows those steps once the window has closed and the program's state is
freed.
"""
from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

from benchmark import compare, manifest, stats

HOST_SPANS = ("etl", "dispatch", "step", "fit", "epoch")


def make_ring(seed: int, n: int, rows: int, image, labels: int) -> List[tuple]:
    """`n` distinct batches from the seed: float32 standard-normal features
    and one-hot labels, as a DataSet holds them. Each batch has a stream of
    its own, so they are drawn side by side (numpy draws outside the GIL):
    the ring is gigabytes, and set-up is paid by every run."""
    eye = np.eye(labels, dtype=np.float32)

    def batch(i: int) -> tuple:
        rng = np.random.default_rng([seed, i])
        return (rng.standard_normal((rows, *image), dtype=np.float32),
                eye[rng.integers(0, labels, rows)])

    with ThreadPoolExecutor(n) as pool:
        return list(pool.map(batch, range(n)))


class RingIterator:
    """Cycles the ring: `count` batches, or until `deadline` (perf_counter)
    when given. One pass; `fit(..., epochs=1)` consumes it."""

    def __init__(self, datasets, count=None, deadline=None, start=0):
        self._sets, self._count, self._deadline = datasets, count, deadline
        self._i = start
        self._n = 0

    def __iter__(self):
        return self

    def reset(self):
        pass

    def async_supported(self):
        return True

    def batch_size(self):
        return self._sets[0].num_examples()

    def __next__(self):
        if self._count is not None and self._n >= self._count:
            raise StopIteration
        if self._deadline is not None and \
                time.perf_counter() >= self._deadline:
            raise StopIteration
        ds = self._sets[self._i % len(self._sets)]
        self._i += 1
        self._n += 1
        return ds


class _Follower:
    """Fit-loop listener for the followed steps: keeps each step's loss
    (on the device), the optimizer's state after the first step, and the
    parameters and BatchNorm's running state after the last, as copies the
    next step cannot donate."""

    def __init__(self, steps: int):
        self.steps, self.losses = steps, []
        self.opt1 = self.params_end = self.state_end = None

    def iteration_done(self, model, iteration):
        import jax
        import jax.numpy as jnp
        self.losses.append(model.score_value)
        if len(self.losses) == 1:
            self.opt1 = jax.tree_util.tree_map(jnp.copy, model.opt_state)
        if len(self.losses) == self.steps:
            self.params_end, self.state_end = jax.tree_util.tree_map(
                jnp.copy, (model.params_tree, model.state_tree))


class _Counter:
    def __init__(self):
        self.steps = 0

    def iteration_done(self, model, iteration):
        self.steps += 1


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {
        f"{layer}/{leaf}": jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32))))
        for layer, d in t.items() for leaf, v in d.items()})(
            {k: d for k, d in tree.items() if d})
    return {k: float(v) for k, v in norms.items()}


class Kind:
    host_spans = HOST_SPANS

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.rows, self.n_ring = int(t["rows"]), int(t["ring"])
        self.follow_steps = int(t.get("follow_steps", 3))
        self.net = self.fit = self._ref = None

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.data.dataset import DataSet
        cfg, seed = self.ctx.cfg, self.ctx.seed
        build = manifest.resolve(cfg["builder"])
        self.net, self.fit = build(cfg, seed, self.ctx.chips)
        ring = make_ring(seed, self.n_ring, self.rows, tuple(cfg["image"]),
                         cfg["labels"])
        self.sets = [DataSet(x, y) for x, y in ring]
        self.params0, self.state0 = jax.tree_util.tree_map(
            jnp.copy, (self.net.params_tree, self.net.state_tree))
        fol = _Follower(self.follow_steps)
        self.net.listeners.append(fol)
        self.fit(RingIterator(self.sets, count=self.follow_steps))
        self.net.listeners.remove(fol)
        if len(fol.losses) != self.follow_steps:
            raise RuntimeError(f"fit ran {len(fol.losses)} steps in set-up, "
                               f"wanted {self.follow_steps}")
        jax.block_until_ready(self.net.score_value)
        self.follower = fol

    # ------------------------------------------------------------ window
    def run(self, seconds: float, probe) -> dict:
        counter = _Counter()
        self.net.listeners.append(counter)
        probe.open()
        t0 = time.perf_counter()
        self.fit(RingIterator(self.sets, deadline=t0 + seconds,
                              start=self.follow_steps))
        score = float(self.net.score_value)     # the fence: the score is read
        t1 = time.perf_counter()
        probe.close()
        self.net.listeners.remove(counter)
        samples = counter.steps * self.rows
        return dict(
            t0=t0, t1=t1, attempted=counter.steps,
            failed=0 if np.isfinite(score) else counter.steps,
            steps=counter.steps, samples=samples, rows=self.rows,
            metrics={"train_samples_per_s": stats.rate(samples, t0, t1)})

    # ------------------------------------------------ after the window
    def program_readings(self):
        """(losses, first-gradient norms, change norms, norms of the change
        of BatchNorm's running state) of the followed steps, from what the
        listener kept. The optimizer is RmsProp: its
        state after one step is (1 - decay) g^2, so the norm of the
        gradient as the optimizer got it is sqrt(sum(state) / (1 - decay))."""
        import jax
        import jax.numpy as jnp
        fol, decay = self.follower, self.ctx.cfg["rms_decay"]
        losses = [float(v) for v in fol.losses]
        grad = {k: v / (1.0 - decay) ** 0.5 for k, v in _leaf_norms(
            jax.tree_util.tree_map(
                lambda s: jnp.sqrt(jnp.maximum(s.astype(jnp.float32), 0.0)),
                fol.opt1)).items()}
        moved = lambda end, start: _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            end, start))
        return (losses, grad, moved(fol.params_end, self.params0),
                moved(fol.state_end, self.state0))

    def temporaries_bytes(self) -> int:
        """Bytes of the step program's temporaries, which the allocator's
        peak may not count: from the step executable's own analysis."""
        try:
            import jax
            net, cfg = self.net, self.ctx.cfg
            abstract = lambda t: jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), t)
            x = jax.ShapeDtypeStruct((self.rows, *cfg["image"]), net._dtype)
            y = jax.ShapeDtypeStruct((self.rows, cfg["labels"]), "float32")
            m = jax.ShapeDtypeStruct((self.rows, 1), "float32")
            out = net.conf.network_outputs[0]
            compiled = net._train_step_fn.lower(
                abstract(net.params_tree), abstract(net.opt_state),
                abstract(net._merged_state()),
                jax.ShapeDtypeStruct((), "int32"), abstract(net._rng),
                {net.conf.network_inputs[0]: x}, {out: y}, {}, {out: m}
            ).compile()
            return int(compiled.memory_analysis().temp_size_in_bytes)
        except Exception as e:   # reported, never fatal: a reading only
            print(f"info temporaries: not read ({type(e).__name__}: {e})",
                  flush=True)
            return 0

    def release(self) -> None:
        self.prog = self.program_readings()
        self.net = self.fit = self.follower = None
        self.params0 = self.state0 = None
        self.sets_for_ref = [(np.asarray(d.features), np.asarray(d.labels))
                             for d in self.sets[:self.follow_steps]]
        self.sets = None
        gc.collect()

    def check(self, control=None) -> Dict[str, float]:
        """The program's followed steps against the reference's. With
        `control` (the builder's tool and the tests, never the benchmark's
        own command) the reference in a lower precision (`float8_e4m3fn`;
        `bfloat16_stored` also keeps parameters and RmsProp state in that
        type) or with a planted fault (`half`: half of each batch left
        out) stands in the program's place."""
        cfg = self.ctx.cfg
        follow = manifest.resolve(cfg["reference"])
        if self._ref is None:
            weights = manifest.resolve(cfg["weights"])(self.ctx.seed, cfg)
            self._ref = (weights, follow(weights, self.sets_for_ref,
                                         cfg["learning_rate"]))
        weights, readings = self._ref
        if control is None:
            return compare.training_gaps(self.prog, readings)
        import jax.numpy as jnp
        if control == "half":
            kw = {"rows": slice(0, self.rows // 2)}
        elif control.endswith("_stored"):
            dtype = jnp.dtype(control[:-len("_stored")])
            kw = {"lowp": dtype, "store": dtype}
        else:
            kw = {"lowp": jnp.dtype(control)}
        return compare.training_gaps(
            follow(weights, self.sets_for_ref, cfg["learning_rate"], **kw),
            readings)
