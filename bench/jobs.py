"""The program side of a cell: k training jobs on one compiled step.

Each job owns its state, made on the device from the seed by the program's
own ``init_train_state``, and its own ``HostPipeline``. All jobs call one
compiled executable (one compile in set-up). The per-step loop is the one of
``repro.launch.train.run``: ``pipeline.get`` -> ``device_put`` -> compiled
step -> ``block_until_ready`` -> ``float(loss)``, here run for a time window
instead of a number of steps. Each job's loop runs on a thread of its own,
from its first step to the end of the window, so the three steps the
comparison reads interleave on the chip as the timed ones do. Host spans
around each call are written into the profiler's trace when one is recording.
Where the traffic names a ``mesh``, the step is the program's sharded one, as
``repro.launch.train --mesh host`` builds it, and each job's state and batches
are laid out by its shardings over the mesh's chips.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs.base import ShapeSuite
from repro.configs.registry import get_config
from repro.data.pipeline import HostPipeline
from repro.launch.mesh import make_mesh_shape
from repro.models.model_api import build_model
from repro.optim import adamw
from repro.runtime import train_step as ts
from repro.sharding.plan import make_plan

import gen

# published config.json keys -> fields of repro's ModelConfig
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "image_size": "img_size", "num_classes": "n_classes",
    "stages": "stages", "base_width": "base_width",
}


def model_config(doc: dict):
    """repro's ModelConfig for a configuration file: the registry entry of
    its ``arch`` with every size the file gives."""
    fields = {f: tuple(doc[k]) if k == "stages" else doc[k]
              for k, f in FIELDS.items() if k in doc}
    return dataclasses.replace(get_config(doc["arch"]), **fields)


def opt_config(traffic: dict) -> adamw.AdamWConfig:
    return adamw.AdamWConfig(**traffic["optimizer"])


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@dataclasses.dataclass
class Job:
    key: jax.Array
    state: Any
    pipeline: HostPipeline
    checked: dict = dataclasses.field(default_factory=dict)
    ends: List[float] = dataclasses.field(default_factory=list)
    failed: int = 0
    wait_s: float = 0.0  # input wait inside the window
    batches: int = 0


class Cell:
    """Model, compiled step and jobs of one (configuration, traffic) cell:
    on one chip, or where the traffic names a ``mesh``, sharded over it."""

    def __init__(self, cfg_doc: dict, traffic: dict, seed: int, out_leaf: str):
        self.cfg_doc, self.traffic, self.out_leaf = cfg_doc, traffic, out_leaf
        self.cfg = model_config(cfg_doc)
        self.model = build_model(self.cfg)
        self.opt = opt_config(traffic)
        suite = ShapeSuite(traffic["name"], traffic.get("seq_len", 0),
                           traffic["batch"], "train")
        make_state = lambda k: ts.init_train_state(self.model, k, self.opt)  # noqa: E731
        mesh = traffic.get("mesh")
        if mesh is None:
            step_fn = ts.build_train_step(self.model, make_plan(self.cfg, None), self.opt)
            jitted = jax.jit(step_fn, donate_argnums=(0,))
            # weights and optimizer state on the device, from the seed, in one call
            init = jax.jit(make_state)
            self.devices, self.batch_sharding, params_sh = jax.devices()[:1], None, None
        else:
            # the launcher's --mesh host path: the program's sharded step, its
            # state made shard by shard, so that no chip holds the whole state
            mesh = make_mesh_shape(mesh["shape"], mesh["axes"])
            jitted, state_sh, self.batch_sharding, _ = ts.jit_train_step(
                self.model, mesh, suite, self.opt)
            init = jax.jit(make_state, out_shardings=state_sh)
            self.devices, params_sh = list(mesh.devices.flat), state_sh["params"]
        self.jobs = []
        for j in range(traffic["jobs"]):
            key = gen.job_key(seed, j)
            dseed = gen.data_seed(seed, j)
            pipe = HostPipeline(
                lambda step, s=dseed: gen.batch(cfg_doc, traffic, s, step),
                workers=traffic["workers"], max_queue_size=traffic["max_queue_size"])
            self.jobs.append(Job(key, init(key), pipe.start()))
        self.compiled = jitted.lower(self.jobs[0].state,
                                     self.model.input_specs(suite)).compile()
        self.names = leaf_names(self.jobs[0].state["params"])
        self._norms = jax.jit(_leaf_norms)

        def fresh(k):  # the initial weights again, laid out as the state's
            p0 = self.model.init(k)
            return p0 if params_sh is None else jax.lax.with_sharding_constraint(p0, params_sh)

        self._change = jax.jit(lambda p, k: _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, fresh(k))))
        self._threads: List[threading.Thread] = []

    def step(self, job: Job) -> float:
        """One step of the launcher's loop; returns the loss."""
        return self._step(job)["loss"]

    def _step(self, job: Job) -> dict:
        with TraceAnnotation("pipeline.get"):
            batch = job.pipeline.get()
        with TraceAnnotation("device_put"):
            batch = jax.device_put(batch, self.batch_sharding)
        with TraceAnnotation("step"):
            job.state, metrics = jax.block_until_ready(self.compiled(job.state, batch))
        with TraceAnnotation("loss"):
            return {k: float(metrics[k]) for k in ("loss", "grad_norm")}

    def _checked_steps(self, job: Job) -> dict:
        """A job's first three steps, with what the comparison reads: each
        loss and global gradient norm before clipping, the first gradient as
        the optimizer got it (from m after one step: m = (1 - b1) g), one
        norm per leaf, and whole for the output layer with the clipping
        undone, and the parameters' change after three steps, one norm per
        leaf."""
        out = [self._step(job)]
        m = job.state["opt"].m
        grad = np.asarray(self._norms(m)) / (1.0 - self.opt.b1)
        clip = min(1.0, self.opt.clip_norm / max(out[0]["grad_norm"], 1e-9))
        out_m = jax.tree_util.tree_leaves(m)[self.names.index(self.out_leaf)]
        out_grad = np.asarray(out_m) / np.float32((1.0 - self.opt.b1) * clip)
        out += [self._step(job), self._step(job)]
        change = np.asarray(self._change(job.state["params"], job.key))
        return {"losses": [o["loss"] for o in out], "gnorms": [o["grad_norm"] for o in out],
                "grad": dict(zip(self.names, grad.tolist())), "out_grad": out_grad,
                "change": dict(zip(self.names, change.tolist()))}

    def _loop(self, job: Job):
        try:
            job.checked = self._checked_steps(job)
            self._ready.wait()
            self._go.wait()
            deadline = self._deadline
            w0 = job.pipeline.stats()
            while True:
                loss = self.step(job)
                t = time.perf_counter()
                job.ends.append(t)
                job.failed += not math.isfinite(loss)
                if t >= deadline:
                    break
            w1 = job.pipeline.stats()
            job.wait_s = w1["input_wait_s"] - w0["input_wait_s"]
            job.batches = int(w1["batches"] - w0["batches"])
        except Exception as e:  # noqa: BLE001 — re-raised in the caller
            self._errors.append(e)
            self._ready.abort()
            self._go.abort()

    def _raise(self):
        errors = [e for e in self._errors if not isinstance(e, threading.BrokenBarrierError)]
        raise (errors or self._errors)[0]

    def checked_steps(self) -> List[dict]:
        """Start every job's loop, all at once, and return the readings of
        each job's first three steps once every job has made them. The loops
        then wait for ``window``."""
        n = len(self.jobs)
        self._ready, self._go = threading.Barrier(n + 1), threading.Barrier(n + 1)
        self._errors: List[BaseException] = []
        self._threads = [threading.Thread(target=self._loop, args=(j,)) for j in self.jobs]
        for t in self._threads:
            t.start()
        try:
            self._ready.wait()
        except threading.BrokenBarrierError:
            self._join()
            self._raise()
        return [j.checked for j in self.jobs]

    def window(self, seconds: float) -> tuple[float, float]:
        """Every job runs on from one start until its first step that ends
        past ``start + seconds``; returns (start, end of the last step)."""
        start = time.perf_counter()
        self._deadline = start + seconds
        try:
            self._go.wait()
        except threading.BrokenBarrierError:
            pass  # a job failed; its error is raised below
        self._join()
        if self._errors:
            self._raise()
        return start, max(j.ends[-1] for j in self.jobs)

    def _join(self):
        for t in self._threads:
            t.join()

    def peak_bytes(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)

    def close(self):
        """Stop the pipelines and free every job's state on the device."""
        for job in self.jobs:
            job.pipeline.stop()
            for x in jax.tree_util.tree_leaves(job.state):
                x.delete()
            job.state = None
