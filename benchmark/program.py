"""The system under test: the one module of the benchmark that imports
the program. It builds the trainer a configuration names, hands it the
weights the benchmark made, and reads its spans and counters; every
number it returns is the program's own, every yardstick is elsewhere.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional


def start(require_tpu: bool) -> dict:
    """Place the compile cache where the program keeps it
    (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is
    set) and report the device."""
    from paddle_tpu.utils import runtime
    if require_tpu:
        return runtime.require_tpu("benchmark")
    return runtime.start("benchmark")


def make_mesh(spec: Optional[dict]):
    if not spec:
        return None
    from paddle_tpu.parallel import mesh as mesh_lib
    return mesh_lib.create_mesh(**{f"n_{k}": int(v)
                                   for k, v in spec.items()})


class Program:
    """One trainer with its feeder: ``train(reader, on_event)`` is
    ``SGD.train`` over the prefetch thread, the call the window times."""

    def __init__(self, cfg: dict, mix: dict, weights: dict, *,
                 compute_dtype="config"):
        import paddle_tpu.data as data
        import paddle_tpu.models as models
        import paddle_tpu.optim as optim
        from paddle_tpu.config import dsl
        from paddle_tpu.trainer import SGD, events

        self.events = events
        self.cfg, self.mix = cfg, mix
        model = cfg["model"]
        dsl.reset()
        cost, _out, self.input_names = getattr(models, model["builder"])(
            *model.get("positional", []), **model["args"])
        opt = cfg["optimizer"]
        self.mesh = make_mesh(mix.get("mesh"))
        if compute_dtype == "config":
            compute_dtype = cfg["precision"]["compute_dtype"]
        self.trainer = SGD(
            cost=cost, parameters=dict(weights),
            update_equation=getattr(optim, opt["name"])(**opt["args"]),
            mesh=self.mesh, compute_dtype=compute_dtype)
        feeding = {name: getattr(data, cfg["inputs"][name]["type"])(
            cfg["inputs"][name]["dim"]) for name in self.input_names}
        self.feeder = data.DataFeeder(
            feeding, pad_multiple=max(int(mix.get("seq_len", 0)), 1))

    def rows(self, batch: Dict) -> list:
        """A generated batch as the rows a reader yields."""
        return list(zip(*(batch[name] for name in self.input_names)))

    def train(self, reader: Callable, on_event: Callable) -> None:
        import jax

        def feeder(rows):
            # the prefetch thread's work, named for the idle gaps
            with jax.profiler.TraceAnnotation("bench_feeder"):
                return self.feeder(rows)

        self.trainer.train(
            reader, feeder=feeder, num_passes=1, event_handler=on_event,
            async_load_data=True)

    # ---------------------------------------------------------- readings
    def params(self) -> dict:
        return self.trainer.params

    def slot(self, name: str) -> dict:
        """One optimizer slot of every trained leaf, ``{leaf: array}``."""
        return {leaf: slots[name]
                for leaf, slots in self.trainer.opt_state["slots"].items()}

    def breakdown(self) -> dict:
        bd = self.trainer.breakdown
        return dict(bd.totals, steps=bd.steps, wall=bd.wall)

    def compiles(self) -> Optional[int]:
        return self.trainer.recompile_guard.count

    def step_memory(self, batch: Dict):
        """``memory_analysis()`` of the compiled train step."""
        return self._compiled(batch).memory_analysis()

    def step_hlo(self, batch: Dict) -> str:
        """The compiled train step's text: every instruction with the
        ``op_name`` path the layers' named scopes wrote (a second
        compile of the step, served by the cache)."""
        return self._compiled(batch).as_text()

    def _compiled(self, batch: Dict):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.parallel import mesh as mesh_lib
        tr = self.trainer
        feed = self.feeder(self.rows(batch))
        if self.mesh is not None:
            feed = mesh_lib.shard_batch(feed, self.mesh)
        return tr._train_step.lower(
            tr.params, tr.opt_state, feed, jax.random.PRNGKey(0),
            jnp.int32(0), None).compile()

    def free(self) -> None:
        """Drop the trainer's state so that the reference has the chip."""
        self.trainer = None
        self.feeder = None


@contextlib.contextmanager
def kernel_tally():
    """Which path each kernel entry took while the step was traced."""
    from paddle_tpu.ops import common
    with common.record_dispatch() as tally:
        yield tally
