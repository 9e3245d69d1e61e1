"""`DistributedDataParallel(...).make_train_step` at world 1: the source
paper's API on one chip, the default `shard_weight_update="auto"` path."""

from __future__ import annotations

import jax

from .. import modelglue
from ._common import next_token_loss, optimizer


class Trainer:
    rows = 1  # rows the forward pass needs for the correctness check

    def __init__(self, model, config, traffic, seed, devices):
        import pytorch_distributed_example_tpu as tdx

        if len(devices) != 1:
            raise ValueError("the ddp trainer adapter drives world 1 only")
        self._tdx = tdx
        tdx.init_process_group(backend="xla", world_size=1)
        variables = modelglue.make_variables(model, config, seed)
        ddp = tdx.DistributedDataParallel(model, variables)
        del variables  # the wrapper holds fresh replicas; free the originals
        self.step = ddp.make_train_step(optimizer(traffic), next_token_loss)
        self.params = ddp.params
        self.opt_state = self.step.init_opt_state(self.params)
        self.device = devices[0]
        self.forward = jax.jit(lambda p, x: model.apply(p, x))

    def place(self, batch):
        return jax.device_put(batch, self.device)

    def close(self):
        self._tdx.destroy_process_group()
