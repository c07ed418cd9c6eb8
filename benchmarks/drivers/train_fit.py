"""Fine-tuning traffic: `Estimator.fit` over a seeded in-memory set, again
and again until the window's seconds have passed.

Set-up builds ONE estimator (`init_orca_context("local")` ->
`Estimator.from_flax` -> the seeded weights through `set_params`),
drives it through its first `check_steps` steps — each a `fit` of one
batch, the window's own call and feed, so that every step's loss comes
back — keeps what the comparison needs (the first step's gradient as
Adam got it, from its first moment; the parameters after the last of
those steps), runs one whole fit, and hands that same estimator to the
window.  The window is whole fits; the rate divides by the time they
really took.

What a cell of this kind varies is in its traffic file (steps per fit,
shuffle, the checked steps) and in the configuration's `estimator`
group (batch, sequence, remat policy, optimiser)."""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.harness import builders

now = time.perf_counter


def leaf_norms(tree) -> Dict[str, float]:
    """The L2 norm of every leaf, by its path.  A leaf stacked over the
    scanned layers (under "blocks") gives one norm per layer, so that a
    fault in one layer is not diluted by the other eleven; a fused
    query-key-value leaf gives one norm per third, so that the key's
    bias — whose gradient is nought under softmax — is a leaf of its
    own and the rule on the reference's gradient can leave it out."""
    import jax
    out = {}

    def thirds(name, a):
        if "qkv" in name.split("/"):
            for part, piece in zip("qkv", np.split(a, 3, axis=-1)):
                out[f"{name}.{part}"] = float(np.sqrt((piece ** 2).sum()))
        else:
            out[name] = float(np.sqrt((a ** 2).sum()))

    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        a = np.asarray(leaf, np.float64)
        if "blocks" in name.split("/"):
            for i, row in enumerate(a):
                thirds(f"{name}[{i}]", row)
        else:
            thirds(name, a)
    return out


def tree_sub(a, b):
    import jax
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        a, b)


def leaf_gaps(got: Dict[str, float], want: Dict[str, float], keep=None
              ) -> Dict[str, float]:
    """Every leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    median = float(np.median(list(want.values())))
    return {name: abs(got[name] - w) / max(w, median)
            for name, w in want.items() if keep is None or name in keep}


def leaf_differences(got, want, keep=None) -> Dict[str, float]:
    """Every leaf's norm of the difference between the program's tree
    and the reference's, against the reference's norm of that leaf or
    of the median leaf, whichever is larger.  First order in rounding
    noise, where the gap of the two norms is second order: the number
    that tells a lower precision from the configuration's."""
    ref = leaf_norms(want)
    median = float(np.median(list(ref.values())))
    return {name: d / max(ref[name], median)
            for name, d in leaf_norms(tree_sub(got, want)).items()
            if keep is None or name in keep}


def worst_and_median(gaps: Dict[str, float]):
    """(the widest gap, its leaf, the median leaf's gap)."""
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))


def first_moment(opt_state):
    """Adam's first moment out of an optax state."""
    import jax
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} Adam states in the optimizer state")
    return found[0].mu


class Driver:
    def __init__(self, config: Dict, traffic: Dict, devices, seed: int):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.est = None
        self._ctx = None

    # -- set-up --------------------------------------------------------

    def fit(self, data) -> Dict:
        """The window's own call; returns the fit's summary."""
        kw = self.config["estimator"]
        self.est.fit(data, epochs=int(self.traffic["epochs_per_fit"]),
                     batch_size=int(kw["batch_size"]),
                     shuffle=bool(self.traffic["shuffle"]))
        return self.est.train_summary[-1]

    def build(self) -> None:
        kw = self.config["estimator"]
        self._ctx = builders.orca_context(self.devices)
        self._ctx.__enter__()
        from analytics_zoo_tpu.common.context import OrcaContext
        OrcaContext.train_data_store = kw["train_data_store"]
        self.est, self.params0 = builders.new_estimator(
            self.config["model"], kw, self.seed)
        self.batches = builders.make_batches(
            self.seed, int(self.traffic["steps_per_fit"]),
            int(kw["batch_size"]), int(kw["seq_len"]),
            self.config["model"]["vocab"])
        self.data = {
            "x": [np.concatenate([b["x"][i] for b in self.batches])
                  for i in range(3)],
            "y": np.concatenate([b["y"] for b in self.batches])}

    def first_steps(self) -> None:
        """The first steps, one fit each, and what the comparison needs
        of the state after them (copied to the host: the step donates
        its state)."""
        import jax
        n = int(self.traffic["check_steps"])
        b1 = float(self.config["estimator"]["adam"]["b1"])
        self.first = dict(losses=[], grad=None, params=None)
        for i, batch in enumerate(self.batches[:n]):
            self.first["losses"].append(float(self.fit(batch)["loss"]))
            if i == 0:
                mu = first_moment(self.est._engine.state.opt_state)
                self.first["grad"] = jax.tree_util.tree_map(
                    lambda m: np.asarray(m) / (1.0 - b1), mu)
        self.first["params"] = jax.tree_util.tree_map(
            np.asarray, self.est._engine.state.params)

    def setup(self) -> None:
        self.build()
        self.first_steps()
        self.fit(self.data)          # one whole fit, every fit-level cost

    # -- the window ----------------------------------------------------

    def window(self, seconds: float, tracer) -> Dict:
        """Whole fits until `seconds` have passed; with a tracer, the
        window's first fit is traced (set-up has run one already)."""
        compiles = builders.compile_seconds()
        rows = len(self.data["y"])
        steps = int(self.traffic["steps_per_fit"])
        seq = int(self.config["estimator"]["seq_len"])
        fits, nan_steps, fit_s = 0, 0, []
        t_open = now()
        while now() - t_open < seconds:
            t = now()
            if tracer is not None and fits == 0:
                with tracer:
                    summary = self.fit(self.data)
            else:
                summary = self.fit(self.data)
            fit_s.append(now() - t)
            fits += 1
            nan_steps += int(summary.get("nan_steps") or 0)
        t_close = now()
        took = t_close - t_open
        self.result = dict(
            t_open=t_open, t_close=t_close, seconds=took, fits=fits, fit_s=fit_s,
            attempted=fits * steps, failed=nan_steps,
            tokens_in_window=fits * rows * seq,
            traced_tokens=rows * seq if tracer is not None else None,
            window_compile_s=builders.compile_seconds() - compiles,
            last_fit_loss=float(summary["loss"]),
            end_to_end={"train_tokens_per_s": fits * rows * seq / took})
        return self.result

    # -- after the window ----------------------------------------------

    def release(self) -> None:
        """Drop the estimator and its state, and the runtime with it."""
        self.est = None
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def reference(self, mode: str = "f32", rows=None, lr=None):
        """(losses, first gradient, parameters after the checked steps)
        of the plain reference over the same batches from the same
        weights, its matmul operands in `mode`; `rows` plants the fault
        of a mean over part of the batch, `lr=0` that of a step which
        returns its state unchanged."""
        from benchmarks.reference import transformer_ref as ref
        kw = self.config["estimator"]
        n = int(self.traffic["check_steps"])
        return ref.train_steps(
            self.params0, self.batches[:n],
            n_head=self.config["model"]["n_head"],
            lr=float(kw["learning_rate"] if lr is None else lr),
            b1=float(kw["adam"]["b1"]),
            b2=float(kw["adam"]["b2"]), eps=float(kw["adam"]["eps"]),
            mode=mode, microbatch=int(self.traffic["reference_microbatch"]),
            rows=rows)

    def compare(self, got: Dict, want) -> Dict[str, float]:
        """The numbers compared: `got` as `first_steps` keeps them,
        `want` as `reference` returns them."""
        losses, grad, params = want
        out = {f"loss_gap_step{i + 1}": abs(g - w) / abs(w)
               for i, (g, w) in enumerate(zip(got["losses"], losses))}
        want_grad = leaf_norms(grad)
        (out["grad_norm_gap_max"], out["grad_norm_gap_leaf"],
         out["grad_norm_gap_median"]) = worst_and_median(
            leaf_gaps(leaf_norms(got["grad"]), want_grad))
        # a leaf whose gradient is nought to rounding in the reference
        # (a key's bias under softmax) moves under Adam by round-off
        # alone: left out of the change, by the reference's gradient
        floor = 1e-3 * float(np.median(list(want_grad.values())))
        moved = {k for k, v in want_grad.items() if v >= floor}
        got_change = tree_sub(got["params"], self.params0)
        want_change = tree_sub(params, self.params0)
        (out["update_norm_gap_max"], out["update_norm_gap_leaf"],
         out["update_norm_gap_median"]) = worst_and_median(leaf_gaps(
            leaf_norms(got_change), leaf_norms(want_change), keep=moved))
        out["grad_diff_median"] = float(np.median(list(
            leaf_differences(got["grad"], grad).values())))
        out["update_diff_median"] = float(np.median(list(
            leaf_differences(got_change, want_change, keep=moved).values())))
        out["leaves_left_out"] = len(want_grad) - len(moved)
        return out

    def check(self, limits: Dict) -> List[Dict]:
        numbers = self.compare(self.first, self.reference())
        checks = []
        for name, spec in limits.items():
            value = numbers[name]
            checks.append(dict(name=name, value=value, limit=spec["limit"],
                               ok=bool(np.isfinite(value))
                               and value <= spec["limit"]))
        return checks
