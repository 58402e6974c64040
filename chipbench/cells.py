"""What a cell is made of, found by name: the configuration file, the
traffic file (the training job), the limits of the comparison, and the
step of the system under test built from them.

Every configuration is ``configs/<name>.json``, every traffic mix
``traffic/<name>.json`` and every cell's limits ``limits/<cell>.json``,
all under this directory; ``BENCHMARK.json`` at the root of the repo names
them. A new cell adds files and one entry there, and edits nothing else.

A configuration file states what it is (``RECORD_KEYS``) and the sizes
that its family's reference (``reference/<family>.py``) reads, listed in
that module's ``SIZES``; each of them is a field of the program's
``ModelConfig`` and overrides the registry's value. A new family brings
its reference, with its ``SIZES``, and its ``flops/<family>.py``, and
edits no file here.

A traffic file holds the job's sizes (``JOB_KEYS``) and, under ``step``,
the keyword arguments of ``repro.launch.train.make_step``, passed to it
as they stand: ``algo`` and the schedule's ``fb_ratio``, ``update_delay``
and ``shifts``, and any engine or kernel choice (``overlap``,
``streams``, ``use_pallas``). A key that neither the harness nor
``make_step`` reads stops the run before anything is built.
"""
from __future__ import annotations

import dataclasses
import importlib
import inspect
import json
import os
from typing import Any, Dict, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# what a configuration file says it is, and its records; its sizes are
# those its family's reference reads
RECORD_KEYS = ("name", "registry", "family", "source", "deployment", "dtype",
               "reduced", "assumed")
# what a traffic file holds besides ``step``
JOB_KEYS = ("workers", "mesh", "batch_per_worker", "seq_len", "optimizer",
            "lr", "step", "why")
# make_step's arguments that the harness itself supplies
BUILT_HERE = ("model", "mesh", "shape", "optimizer", "schedule")


def _read(kind: str, name: str, base: Optional[str] = None) -> Dict[str, Any]:
    path = os.path.join(base or HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Optional[str] = None) -> Dict[str, Any]:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"chipbench: no workload {workload!r} in BENCHMARK.json")


def family_module(kind: str, cdict: Dict[str, Any]):
    """``chipbench/<kind>/<family>.py`` (``reference``, ``flops``), chosen
    by the configuration's family."""
    return importlib.import_module(f"chipbench.{kind}.{cdict['family']}")


def config_dict(name: str, base: Optional[str] = None) -> Dict[str, Any]:
    """The configuration file, refused unless it states every size its
    family's reference reads, each one a size the program has, and
    nothing else but its records."""
    from repro.configs import ModelConfig
    d = _read("configs", name, base)
    sizes = family_module("reference", d).SIZES
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    family = d["family"]
    no_field = sorted(set(sizes) - fields)
    if no_field:
        raise ValueError(f"the {family} reference reads {no_field}, which "
                         "the program has no size for")
    unknown = sorted(set(d) - set(RECORD_KEYS) - set(sizes))
    if unknown:
        why = "; ".join(
            f"{k}: the {family} reference does not read it" if k in fields
            else f"{k}: the program has no such size" for k in unknown)
        raise ValueError(f"config {name}: nothing reads {unknown} ({why})")
    missing = [k for k in sizes if k not in d]
    if missing:
        raise ValueError(f"config {name} does not state {missing}, which "
                         f"the {family} reference reads")
    return d


def load_traffic(name: str, base: Optional[str] = None) -> Dict[str, Any]:
    """The job, refused where it holds a key that nothing reads."""
    from repro.launch.train import make_step
    job = _read("traffic", name, base)
    takes = set(inspect.signature(make_step).parameters) - set(BUILT_HERE)
    unknown = sorted(set(job) - set(JOB_KEYS)) + sorted(
        f"step.{k}" for k in set(job["step"]) - takes)
    if unknown:
        raise ValueError(f"traffic {name}: nothing reads {unknown}")
    if "algo" not in job["step"]:
        raise ValueError(f"traffic {name}: step does not name its algo")
    return job


def load_limits(cell: str, base: Optional[str] = None) -> Dict[str, Any]:
    return _read("limits", cell, base)


def load_config(name: str, base: Optional[str] = None, **override):
    """The registry's ModelConfig run at the file's sizes: every size the
    file states is the size that runs."""
    from repro.configs import get_config
    import jax.numpy as jnp
    d = dict(config_dict(name, base), **override)
    sizes = family_module("reference", d).SIZES
    return get_config(d["registry"]).with_(
        dtype=jnp.dtype(d["dtype"]), **{k: d[k] for k in sizes})


def seed_key(seed: int) -> int:
    """A 32-bit weights key from any whole-number seed."""
    return int(np.random.SeedSequence([seed % 2**64, 0]).generate_state(1)[0]
               % 2**31)


def optimizer_for(cfg, job):
    from repro.optim import momentum
    opt = job["optimizer"]
    if opt["name"] != "momentum":
        raise ValueError(f"unsupported optimizer {opt['name']!r}")
    return momentum(float(opt["beta"]), state_dtype=cfg.dtype)


def build_step(cfg, job, mesh):
    """The system under test: ``make_step`` as a training job calls it,
    with the traffic file's ``step`` arguments as they stand."""
    from repro.configs import ShapeConfig
    from repro.launch.train import make_step
    from repro.models import build_model
    from repro.optim import constant
    shape = ShapeConfig("chipbench", int(job["seq_len"]),
                        int(job["batch_per_worker"]) * int(job["workers"]),
                        "train")
    step = dict(job["step"])
    if "shifts" in step:
        step["shifts"] = tuple(step["shifts"])
    return make_step(build_model(cfg), mesh, shape,
                     optimizer=optimizer_for(cfg, job),
                     schedule=constant(float(job["lr"])), **step)


def first_applied_step(job) -> int:
    """The first step whose optimizer update carries a gradient: the
    FIFO holds zeros for the first ``update_delay`` steps."""
    return int(job["step"].get("update_delay", 0))


class Program:
    """The step of the system under test and its state, built once from
    the seed; the checked first steps and the timed window both drive
    ``step``. ``built`` is what ``build_step`` returned, or that step
    compiled once for several programs: a step that compiles whole, or an
    overlap engine (``init_state``) whose stages compile on their first
    calls."""

    def __init__(self, cfg, job, mesh, built, seed: int):
        import jax
        from repro.models import build_model
        self.cfg, self.job, self.mesh = cfg, job, mesh
        self.model = build_model(cfg)
        self.layup = job["step"]["algo"] == "layup"
        self.M = int(job["workers"])
        self.shifts = len(job["step"].get("shifts", [1]))
        self.key = jax.random.PRNGKey(seed_key(seed))
        self.engine = built if hasattr(built, "init_state") else None
        if self.engine is None:
            # a step not yet compiled, or one compiled for several runs
            self.compiled = (built.lower().compile()
                             if hasattr(built, "lower") else built)
            state_sh = self.compiled.input_shardings[0][0]
            if self.layup and not (isinstance(state_sh, dict)
                                   and "read" in state_sh):
                raise ValueError("the harness drives LayUp's decoupled lane "
                                 "(fb_ratio > 1, update_delay > 0 or "
                                 "overlap)")
            self.batch_sharding = self.compiled.input_shardings[0][
                1 if self.layup else 2]
        else:
            self.compiled = None
            self.batch_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data"))
        self.state = self._init_state()
        if self.layup:
            from repro.core.layerview import FlatPartition
            self.part = FlatPartition(self.model.abstract_params())

    # -- state ------------------------------------------------------------

    def _init_state(self):
        import jax
        import jax.numpy as jnp
        opt = optimizer_for(self.cfg, self.job)
        if self.engine is not None:
            p = jax.jit(self.model.init)(self.key)
            return self.engine.init_state(
                jax.tree.map(lambda x: jnp.stack([x] * self.M), p))
        sh = self.compiled.input_shardings[0]
        if not self.layup:
            def build(key):
                p = self.model.init(key)
                return p, opt.init(p)
            return jax.jit(build, out_shardings=(sh[0], sh[1]))(self.key)

        from repro.launch.train import make_decoupled_state
        D, M = first_applied_step(self.job), self.M

        def build(key):
            p = self.model.init(key)
            st = make_decoupled_state(jax.tree.map(lambda x: x[None], p),
                                      opt, update_delay=D)
            st["w"] = jnp.full((1,), 1.0 / M, jnp.float32)
            return st

        # one program over the mesh: each chip builds its own worker's
        # shard from the same key, so no chip ever holds another worker's
        # state, and the compile is that of one worker's state
        specs = jax.tree.map(lambda x: x.spec, sh[0])
        fn = jax.jit(jax.shard_map(build, mesh=self.mesh,
                                   in_specs=jax.sharding.PartitionSpec(),
                                   out_specs=specs, check_vma=False),
                     out_shardings=sh[0])
        return fn(self.key)

    def compiled_bytes(self) -> Optional[int]:
        """Argument + temporary bytes of the compiled step on one chip;
        ``None`` for an engine, whose stages compile apart."""
        ma = self.compiled.memory_analysis() if self.compiled else None
        if ma is None:
            return None
        return ma.argument_size_in_bytes + ma.temp_size_in_bytes

    # -- readings ---------------------------------------------------------

    def _norms(self, which: str, start=None):
        """Per-leaf, per-worker norms of the parameters (LayUp's
        ``"write"`` or ``"read"`` plane, DDP's parameters) less ``start``
        when given, or of the optimizer's state (``"opt"``). The plane is
        read in place: its tree view is taken inside the one compiled
        reduction, which both of LayUp's planes share."""
        import jax
        import jax.numpy as jnp
        if not hasattr(self, "_norm_fn"):
            layup, part = self.layup, getattr(self, "part", None)

            def norms(x, start):
                t = part.unpack(x) if layup else jax.tree.map(
                    lambda a: a[None], x)
                if start is not None:
                    t = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                     - b[None].astype(jnp.float32), t, start)
                return jax.tree.map(
                    lambda a: jnp.sqrt(jnp.sum(
                        jnp.square(a.astype(jnp.float32)),
                        axis=tuple(range(1, a.ndim)))), t)

            self._norm_fn = jax.jit(norms)
        x = (self.state[which] if self.layup
             else self.state[1 if which == "opt" else 0])
        out = jax.device_get(self._norm_fn(x, start))
        flat, _ = jax.tree_util.tree_flatten_with_path(out)
        return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
                for p, v in flat}

    def param_change_norms(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Norms of the parameters' change since the seed's weights, of
        each copy the step holds: ``d_norms``, LayUp's write plane or
        DDP's parameters, and ``d_norms_read``, the read plane that the
        next forward takes."""
        import jax
        self.wait()
        start = jax.jit(self.model.init)(self.key)
        out = {"d_norms": self._norms("write", start)}
        if self.layup:
            out["d_norms_read"] = self._norms("read", start)
        del start
        return out

    def opt_norms(self):
        self.wait()
        return self._norms("opt")

    def versions(self) -> Optional[np.ndarray]:
        """LayUp's per-worker, per-group version clocks; ``None`` for DDP."""
        import jax
        if not self.layup:
            return None
        self.wait()
        return np.asarray(jax.device_get(self.state["versions"]), np.float64)

    # -- one step -----------------------------------------------------------

    def put_batch(self, host_batch):
        import jax
        return jax.device_put(host_batch, self.batch_sharding)

    def step(self, batch, t: int):
        """Dispatch step ``t``; returns its loss, which ``float`` reads."""
        import jax.numpy as jnp
        if self.engine is not None:
            self.state, m = self.engine.fn(self.state, batch, t,
                                           t % self.shifts)
            return m["loss"]
        if self.layup:
            self.state, m = self.compiled(self.state, batch, jnp.int32(t),
                                          jnp.int32(t % self.shifts))
            return m["loss"]
        p, o, loss = self.compiled(self.state[0], self.state[1], batch,
                                   jnp.int32(t))
        self.state = (p, o)
        return loss

    def wait(self):
        """Block until every dispatched step has finished."""
        import jax
        engine = getattr(self.engine, "engine", None)
        if hasattr(engine, "materialize"):   # the stream engine's futures
            self.state = engine.materialize(self.state)
        jax.block_until_ready(self.state)

    def free(self):
        engine = getattr(self.engine, "engine", None)
        if engine is not None:
            engine.close() if hasattr(engine, "close") else engine.reset()
        self.state = None
