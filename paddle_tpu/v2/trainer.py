"""v2 trainer (`python/paddle/v2/trainer.py`): SGD with the v2 signature.

``feeding`` accepts either {name: data_type} (builds a DataFeeder) or a
ready DataFeeder. Reader items are sample tuples in feeding order, as in
the reference's DataFeeder protocol.
"""

from __future__ import annotations

from typing import Optional

from paddle_tpu.data.feeder import DataFeeder
from paddle_tpu.data.types import InputType
from paddle_tpu.trainer.trainer import SGD as _SGD
from paddle_tpu.trainer.trainer import Topology  # noqa: F401


class SGD(_SGD):
    def __init__(self, cost, parameters=None, update_equation=None,
                 **kwargs):
        if hasattr(parameters, "_params"):  # v2 Parameters object
            import jax.numpy as jnp
            parameters = {k: jnp.asarray(v)
                          for k, v in parameters._params.items()}
        # paddle.init(...) flags become trainer defaults, the way the
        # reference's gflags reach Trainer::init (`utils/Flags.cpp:18-80`):
        # trainer_count>1 selects a data-parallel mesh (the
        # MultiGradientMachine thread fan-out, `MultiGradientMachine.h:44`),
        # seed seeds parameter init, log_period paces train logging.
        from paddle_tpu import v2 as _v2
        flags = _v2.init_flags()
        if "seed" in flags:
            kwargs.setdefault("seed", int(flags["seed"]))
        if kwargs.get("mesh") is None and int(
                flags.get("trainer_count", 1) or 1) > 1:
            import jax as _jax

            from paddle_tpu.parallel import create_mesh
            want = int(flags["trainer_count"])
            have = len(_jax.devices())
            if want > have:
                raise ValueError(
                    f"paddle.init(trainer_count={want}) but this process "
                    f"has {have} device(s) — refusing to train narrower "
                    "than asked")
            kwargs["mesh"] = create_mesh(
                n_data=want, devices=_jax.devices()[:want])
            self._mesh_from_flags = True
        super().__init__(cost, parameters=parameters,
                         update_equation=update_equation, **kwargs)

    def train(self, reader, *, num_passes: int = 1, event_handler=None,
              feeding=None, **kwargs):
        from paddle_tpu import v2 as _v2
        flags = _v2.init_flags()
        if "log_period" in flags:
            kwargs.setdefault("log_period", int(flags["log_period"]))
        reader = self._trim_to_dp_degree(reader)
        feeder = feeding
        if isinstance(feeding, dict):
            if not all(isinstance(v, InputType) for v in feeding.values()):
                raise TypeError(
                    "feeding must map data-layer names to paddle.data_type "
                    "objects (the index-based v2 form is not supported; "
                    "order the reader columns by the feeding dict instead)")
            feeder = DataFeeder(feeding)
        return super().train(reader, feeder=feeder, num_passes=num_passes,
                             event_handler=event_handler, **kwargs)

    def test(self, reader, *, feeding=None, **kwargs):
        feeder = feeding
        if isinstance(feeding, dict):
            feeder = DataFeeder(feeding)
        reader = self._trim_to_dp_degree(reader)
        return super().test(reader, feeder=feeder, **kwargs)

    def _trim_to_dp_degree(self, reader):
        """When the mesh came from paddle.init(trainer_count=N) rather than
        an explicit mesh argument, ragged final batches (paddle.batch
        defaults to drop_last=False) must not crash — trim them to the DP
        degree like a drop-remainder, with a one-time warning."""
        if not getattr(self, "_mesh_from_flags", False):
            return reader
        from paddle_tpu.parallel import mesh as _mesh_lib
        n = _mesh_lib.data_parallel_degree(self.mesh)
        warned = [False]

        def trimming_reader():
            for batch in reader():
                extra = len(batch) % n
                if extra:
                    if not warned[0]:
                        warned[0] = True
                        from paddle_tpu.utils.log import logger
                        logger.warning(
                            "dropping %d sample(s) from a batch of %d "
                            "not divisible by trainer_count=%d",
                            extra, len(batch), n)
                    batch = batch[:len(batch) - extra]
                if batch:
                    yield batch

        return trimming_reader
