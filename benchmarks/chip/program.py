"""The system under test, built through the program's own functions.

Everything the benchmark takes from the program goes through here: the
model, mesh, planner, optimizer, comm engine and the jitted train step,
built with the `repro.launch` / `repro.train.trainer` functions that
`launch/train.py:run` calls, and the batches of `data/pipeline.py`. The
weights come from the benchmark's own `weights` module, never from the
program's initializer.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import plain
import weights

FAULTS = ("frozen_state", "half_batch", "no_exchange", "no_error_feedback")


def _path(keypath) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in keypath)


def _get(obj, dotted: str):
    for part in dotted.split("."):
        obj = None if obj is None else getattr(obj, part)
    return obj


def _replace(obj, changes: dict):
    """dataclasses.replace, into nested dataclasses for dict values."""
    kw = {k: _replace(getattr(obj, k), v) if isinstance(v, dict) else v
          for k, v in changes.items()}
    return dataclasses.replace(obj, **kw)


def _same(a, b) -> bool:
    if isinstance(a, tuple):
        a = list(a)
    if isinstance(a, type):
        a = np.dtype(a).name
    return a == b


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: its registry
    entry with `program.replace` applied, checked against every size the
    file states and every property the reference assumes."""
    from repro.configs import registry
    prog = cfg["program"]
    mc = _replace(registry.get_config(prog["arch"]), prog["replace"])
    wrong = {attr: (_get(mc, attr), cfg[key])
             for attr, key in prog["same_as"].items()
             if not _same(_get(mc, attr), cfg[key])}
    wrong.update({attr: (_get(mc, attr), want)
                  for attr, want in prog["expect"].items()
                  if not _same(_get(mc, attr), want)})
    if wrong:
        raise ValueError(f"{cfg['name']}: the program's configuration "
                         f"differs from the file: {wrong}")
    return mc


class Program:
    """One cell's training step and its state, from the configuration file
    `cfg`, the traffic file `traffic` and the reference's parameter specs."""

    def __init__(self, cfg: dict, traffic: dict, specs: dict):
        from repro.core import planner as pl
        from repro.launch import mesh as mesh_lib
        from repro.models.transformer import Model
        from repro.optim import optimizers as opt_lib
        from repro.train import trainer as tr

        self.cfg, self.traffic = cfg, traffic
        self.model = Model(model_config(cfg))
        shape = traffic["mesh"]
        if "node" in shape:
            self.mesh = mesh_lib.make_hier_mesh(shape["node"], shape["local"])
        else:
            self.mesh = mesh_lib.make_host_mesh(shape["data"])
        planner = pl.Planner(mesh=self.mesh)
        opt = cfg["optimizer"]
        self.optimizer = opt_lib.make_optimizer(
            opt["name"], opt["lr"], b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"])
        comm = tr.CommConfig(**traffic["comm"])
        self.engine = (tr.make_comm_engine(self.model, self.mesh, planner,
                                           comm)
                       if comm.mode == "mlsl" else None)
        state_sh = tr.state_shardings(planner, self.model, self.optimizer,
                                      self.engine)
        self.batch_sh = tr.batch_shardings(planner, self.model,
                                           traffic["batch"])
        self.step = jax.jit(
            tr.make_train_step(self.model, self.optimizer, self.mesh,
                               planner, comm, grad_clip=opt["clip"],
                               engine=self.engine),
            out_shardings=(state_sh, NamedSharding(self.mesh, P())),
            donate_argnums=0)

        # the program's parameter tree must be the reference's, leaf by leaf
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(shapes)
        self.paths = [_path(kp) for kp, _ in flat]
        mine = {p: (tuple(x.shape), x.dtype.name)
                for p, (_, x) in zip(self.paths, flat)}
        theirs = {p: (tuple(s), cfg["param_dtype"])
                  for p, (s, _) in specs.items()}
        if mine != theirs:
            raise ValueError(f"{cfg['name']}: the program's parameters "
                             f"{mine} are not the reference's {theirs}")
        gen = weights.generator(specs, jax.numpy.dtype(cfg["param_dtype"]))
        model, optimizer, engine = self.model, self.optimizer, self.engine

        def init(lo, hi):
            made = gen(lo, hi)
            state = tr.make_train_state(model, optimizer,
                                        jax.random.PRNGKey(0), engine=engine)
            return dataclasses.replace(state, params=self._tree(made))

        self._init = jax.jit(init, out_shardings=state_sh)
        n_layers = cfg["num_hidden_layers"]
        change = plain.change_fn(specs, jax.numpy.dtype(cfg["param_dtype"]),
                                 n_layers)
        self._change_norms = jax.jit(
            lambda params, lo, hi: change(self.leaves(params), lo, hi))
        b1 = opt["b1"]
        # AdamW's first moment after one step is (1 - b1) g
        self._grad_norms = jax.jit(lambda m: plain.leaf_norms(
            {k: x / (1 - b1) for k, x in self.leaves(m).items()}, n_layers))
        self._residual_norms = self._residual_reader(n_layers)

    def _residual_reader(self, n_layers: int):
        """A jitted reader of the error-feedback residuals the state
        carries per bucket, as norms per leaf (a stacked leaf per layer)
        in units of the mean gradient, or None without error feedback.

        A bucket's residual holds, at each chip's place along its first
        dim (the data axes in order), that chip's chunk of the padded
        bucket: the chunks are cut by the reduce-scatters, over `local`
        then `node` on the two-level route and over the data axes on the
        flat one, and they hold the sum over the chips, hence / dp."""
        from repro.core import planner as pl
        engine = self.engine
        if engine is None or not engine.plan.use_ef:
            return None
        p = engine.plan
        axes = tuple(p.data_axes)
        shape = {a: self.mesh.shape[a] for a in axes}
        held = jax.eval_shape(engine.init_residuals)
        spans = {}                   # unit -> [(bucket, start, end)]
        for bi, bucket in enumerate(p.buckets.buckets):
            if not engine.ef_applied(bi):
                continue
            scatter = (("local", "node") if p.algos[bi] == pl.ALGO_HIER
                       else axes)
            chunk = int(np.prod(held[bi].shape)) // p.dp
            where = {}               # chunk index -> place along dim 0
            for coords in np.ndindex(*(shape[a] for a in axes)):
                at = dict(zip(axes, coords))
                where[int(np.ravel_multi_index(
                    [at[a] for a in scatter],
                    [shape[a] for a in scatter]))] = int(
                    np.ravel_multi_index(coords, [shape[a] for a in axes]))
            off = 0
            for lid, size, shp in zip(bucket.leaf_ids, bucket.sizes,
                                      bucket.shapes):
                path = self.paths[lid]
                per = (shp[0] if path.startswith("blocks/") and shp
                       and shp[0] == n_layers else 0)
                units = ([(f"{path}[{i}]", off + i * size // per,
                           off + (i + 1) * size // per) for i in range(per)]
                         if per else [(path, off, off + size)])
                for name, a, b in units:
                    for c in range(a // chunk, (b - 1) // chunk + 1):
                        lo, hi = max(a, c * chunk), min(b, (c + 1) * chunk)
                        base = where[c] * chunk - c * chunk
                        spans.setdefault(name, []).append(
                            (bi, base + lo, base + hi))
                off += size
        dp = p.dp

        def read(residuals):
            flat = [r.reshape(-1) for r in residuals]
            return {name: jax.numpy.sqrt(sum(
                jax.numpy.sum(flat[bi][a:b] ** 2) for bi, a, b in parts)) / dp
                for name, parts in spans.items()}
        return jax.jit(read)

    def _tree(self, by_path: dict):
        return jax.tree_util.tree_unflatten(
            self._treedef, [by_path[p] for p in self.paths])

    def leaves(self, tree) -> dict:
        return dict(zip(self.paths, jax.tree_util.tree_leaves(tree)))

    # --- running ----------------------------------------------------------------

    def init_state(self, seed: int):
        """The train state with the seed's weights, built on the devices in
        the layout the step keeps it in."""
        with jax.set_mesh(self.mesh):
            return self._init(*weights.seed_words(seed))

    def feed(self, dcfg, step: int):
        """Step `step`'s batch from the pipeline: (host tokens, device
        batch)."""
        from repro.data import pipeline
        from repro.models.transformer import Batch
        raw = pipeline.batch_at(dcfg, step)
        return raw["tokens"], jax.device_put(
            Batch(tokens=raw["tokens"], labels=raw["labels"]), self.batch_sh)

    def first_grad_norms(self, state) -> dict:
        """Norms of the gradient AdamW took in the first step, from its
        first moment."""
        norms = self._grad_norms(state.opt_state["m"])
        return {k: float(x) for k, x in norms.items()}

    def residual_norms(self, state):
        """Norms of the int8 wire's error-feedback residual, per leaf in
        the mean gradient's units (None without error feedback)."""
        if self._residual_norms is None:
            return None
        norms = self._residual_norms(state.comm_residuals)
        return {k: float(x) for k, x in norms.items()}

    def change_norms(self, state, seed: int) -> dict:
        """Norms of the parameters' change from the seed's weights."""
        with jax.set_mesh(self.mesh):
            norms = self._change_norms(state.params,
                                       *weights.seed_words(seed))
        return {k: float(x) for k, x in norms.items()}

    def plan(self) -> dict:
        """The comm engine's plan as plain data: what the exchange moves."""
        if self.engine is None:
            return {"buckets": []}
        from repro.core import collectives
        p = self.engine.plan
        hier = p.hier_spec
        return {
            "quant_block": collectives.QUANT_BLOCK,
            "dp": p.dp, "n_node": p.n_node, "n_local": p.n_local,
            "wire": p.wire, "wire_intra": hier.wire_intra if hier else None,
            "error_feedback": p.use_ef,
            "buckets": [{"elems": b.n_elems, "route": str(a),
                         "fusable": bool(f)}
                        for b, a, f in zip(p.buckets.buckets, p.algos,
                                           p.fusable)],
        }


def data_config(cfg: dict, traffic: dict, seed: int):
    """The pipeline's synthetic stream for this configuration and job."""
    from repro.data import pipeline
    return pipeline.DataConfig(vocab=cfg["vocab_size"], seq_len=traffic["seq"],
                               global_batch=traffic["batch"], seed=seed)


def fed_tokens(cfg: dict, traffic: dict, seed: int, steps: int) -> list:
    """The host tokens of the pipeline's first `steps` batches."""
    from repro.data import pipeline
    dcfg = data_config(cfg, traffic, seed)
    return [pipeline.batch_at(dcfg, s)["tokens"] for s in range(steps)]


def free(tree) -> None:
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()


@contextlib.contextmanager
def planted(fault: str | None):
    """Break the program underneath the benchmark (for the checks that
    `correct` catches each fault): enter before the Program is built.

    frozen_state: the step returns its state unchanged.
    half_batch: the loss sees half of each chip's batch (half the rows, or
      half the positions of a single row) and takes the mean over it.
    no_exchange: the engine hands back each chip's own gradients.
    no_error_feedback: the int8 wire's residual is never carried: every
      step quantizes its gradient alone and keeps the residual at zero."""
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    from repro.core import collectives as cl
    from repro.core.engine import CommEngine
    from repro.models.transformer import Model
    from repro.train import trainer as tr

    saved = []

    def patch(owner, name, new):
        saved.append((owner, name, getattr(owner, name)))
        setattr(owner, name, new)

    if fault == "frozen_state":
        make = tr.make_train_step

        def make_frozen(*a, **kw):
            step = make(*a, **kw)

            def frozen(state, batch):
                _, metrics = step(state, batch)
                return state, metrics
            return frozen
        patch(tr, "make_train_step", make_frozen)
    elif fault == "half_batch":
        loss = Model.loss

        def half(self, params, batch, **kw):
            B, S = batch.tokens.shape
            cut = (lambda x: x[: B // 2]) if B > 1 else \
                (lambda x: x[:, : S // 2])
            return loss(self, params, dataclasses.replace(
                batch, tokens=cut(batch.tokens), labels=cut(batch.labels)),
                **kw)
        patch(Model, "loss", half)
    elif fault == "no_exchange":
        patch(CommEngine, "reduce", lambda self, grads, res: (grads, res))
    else:
        allreduce_ef = cl.allreduce_ef

        def no_feedback(x, residual, axes, **kw):
            out, _ = allreduce_ef(x, jax.numpy.zeros_like(residual), axes,
                                  **kw)
            return out, residual
        patch(cl, "allreduce_ef", no_feedback)
    try:
        yield
    finally:
        for owner, name, old in reversed(saved):
            setattr(owner, name, old)
