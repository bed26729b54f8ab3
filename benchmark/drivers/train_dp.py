"""Traffic generator ``train_dp``: ``train_fit``'s closed loop through
``ParallelWrapper`` on the chips of one host.

The trainer wraps the network as the reference's ``ParallelWrapper`` users
did, ``ParallelWrapper.builder(net).workers(<chips>).build().fit(iterator)``
with ``averaging_frequency`` 1: synchronous data parallelism, one jitted
K-step program over a global batch sharded on the mesh's ``data`` axis, the
parameters replicated. Batch-norm statistics are therefore over the global
batch, and the plain reference follows the same steps at the global batch,
unchanged. Parameters from the traffic file are ``train_fit``'s; ``batch`` is
the global batch. The wrapper's synchronous loop is the networks' own staged
loop (``LazyScore._fit_epoch`` with the wrapper as its ``LoopOwner``): each
batch is cast to the network's ``stage_dtype`` (the traffic's, set by
``train_fit``'s ``build``) into a slot of a host ring the wrapper keeps, and
every chip is sent its shard of the K-step group. The groups carry the same
spans as a network's (``input.pull|stack|cast|h2d``, ``fit.dispatch`` with the
all-reduce's ``collective_bytes``), so the span metrics read this cell too.
"""
from __future__ import annotations

from drivers import train_fit

make_pool = train_fit.make_pool


class Driver(train_fit.Driver):
    def build(self, weights: dict):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        net = super().build(weights)
        self.wrapper = (ParallelWrapper.builder(net)
                        .workers(int(self.cell["chips"]))
                        .prefetch_buffer(int(self.traffic["prefetch_depth"]))
                        .averaging_frequency(1)
                        .build())
        # a wrapper from before ``ParallelWrapper._place_state`` takes the
        # state as it is handed over: on one device, the second dispatch sees
        # another placement than the first and the step compiles again,
        # inside the window. The benchmark's files run on such a program too
        # (the parent commit), so the state is laid out over the mesh here;
        # where the wrapper does it itself this moves nothing
        everywhere = NamedSharding(self.wrapper.mesh, PartitionSpec())
        net.params_list, net.state_list, net.updater_state = jax.device_put(
            (net.params_list, net.state_list, net.updater_state), everywhere)
        return net

    def fit(self, iterator):
        self.wrapper.fit(iterator)

    def release(self):
        self.wrapper = None
        super().release()
