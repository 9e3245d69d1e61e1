"""`fully_shard` over a device mesh, one process driving every chip. The
weights are made already sharded (`jit(init, out_shardings=...)` with the
specs `make_param_specs` gives for the shapes), so the whole tree never
sits on one chip and `fully_shard`'s own device_put moves nothing."""

from __future__ import annotations

import copy

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import modelglue
from ._common import next_token_loss, optimizer


class Trainer:
    def __init__(self, model, config, traffic, seed, devices):
        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.models import (
            transformer_sharding_rules,
        )
        from pytorch_distributed_example_tpu.parallel import fully_shard
        from pytorch_distributed_example_tpu.parallel.sharding import (
            make_param_specs,
        )

        m = traffic["mesh"]
        if int(np.prod(m["shape"])) != len(devices):
            raise ValueError(f"mesh {m['shape']} over {len(devices)} devices")
        mesh = init_device_mesh(tuple(m["axes"]), tuple(m["shape"]), devices=devices)
        jmesh = getattr(mesh, "jax_mesh", mesh)
        data_axis, tp_axis = m["axes"]
        rules = transformer_sharding_rules(tp_axis, data_axis)
        shapes = jax.eval_shape(modelglue.init_fn(model, config), jax.random.PRNGKey(0))
        specs = make_param_specs(shapes, rules, jmesh)
        shardings = jax.tree_util.tree_map(lambda s: NamedSharding(jmesh, s), specs)
        variables = modelglue.make_variables(model, config, seed, shardings)
        mod = fully_shard(
            model, variables, mesh, axis=data_axis, rules=rules,
            data_axes=(data_axis,),
        )
        del variables
        self.step = mod.make_train_step(optimizer(traffic), next_token_loss)
        self.params = mod.params
        self.opt_state = self.step.init_opt_state(self.params)
        self.rows = jmesh.shape[data_axis]
        self._batch = NamedSharding(jmesh, P(data_axis))
        self.device = devices[0]

        def forward(params, x):
            # the module's own __call__, which opens the kernel partition
            # for the flash kernel; it reads params from the module
            m2 = copy.copy(mod)
            m2.params = params
            return m2(x)

        self.forward = jax.jit(forward)

    def place(self, batch):
        return jax.device_put(batch, self._batch)

    def close(self):
        pass
