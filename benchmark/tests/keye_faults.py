"""Faults of a cell whose attention selects its keys, planted where each can
be: in the PROGRAM under the cell's own driver (``broken``, for
``drive_keye_faults.py`` and the tests) and in the REFERENCE put in the
program's place (``faulty_reference``, for ``probe_select.py`` on the chip,
where a second program would cost a compile).

* ``dense_core``: the selection left out; every query attends to every key
  before it (the indexer's loss then runs over all of them too).
* ``topk_halved``: each query keeps half the keys the configuration states.
* ``no_index_loss``: the indexer's loss left out of the step's.
* ``no_renorm``: the router's chosen probabilities not divided by their sum.
* ``half_batch``: half of what a step trains on repeats the other half: half
  of the batch's sequences, or of a single sequence's tokens.
"""
import copy
import importlib

FAULTS = ("dense_core", "topk_halved", "no_index_loss", "no_renorm",
          "half_batch")


def faulty_kwargs(kwargs: dict, fault: str) -> dict:
    """The builder's (and the reference's) kwargs with ``fault`` planted,
    for the faults that are a number of the configuration."""
    kw = dict(kwargs)
    if fault == "topk_halved":
        kw["index_topk"] = kw["index_topk"] // 2
    elif fault == "no_index_loss":
        kw["index_loss_weight"] = 0.0
    elif fault == "no_renorm":
        kw["norm_topk_prob"] = False
    return kw


def half_repeated(pool):
    """A copy of ``pool`` with the second half of every batch (a batch of
    one: of its sequence's positions) repeating the first."""
    out = []
    for ds in pool:
        ds = copy.deepcopy(ds)
        axis = 0 if ds.features.shape[0] > 1 else 1
        n = ds.features.shape[axis] // 2
        for a in (ds.features, ds.labels):
            if axis == 0:
                a[n:2 * n] = a[:n]
            else:
                a[:, n:2 * n] = a[:, :n]
        out.append(ds)
    return out


def all_causal(scores, topk, *, interpret=False):
    """``indexer.select_topk``'s place with the selection left out."""
    import jax
    import jax.numpy as jnp

    T = scores.shape[1]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    lse = jax.nn.logsumexp(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8), lse


def broken(cell: dict, fault: str):
    """The cell's driver with ``fault`` planted in the program's path; the
    reference follows the sound configuration and pool."""
    drivers = importlib.import_module("drivers." + cell["traffic"]["driver"])

    class Broken(drivers.Driver):
        def build(self, weights):
            sound = self.kwargs
            self.kwargs = faulty_kwargs(sound, fault)
            try:
                if fault != "dense_core":
                    return super().build(weights)
                from deeplearning4j_tpu.ops import indexer

                # the step program is traced at the first dispatch, long
                # after build: the patch stays for the process
                indexer.select_topk = all_causal
                return super().build(weights)
            finally:
                self.kwargs = sound

        def fit(self, iterator):
            if fault == "half_batch":
                iterator.pool = half_repeated(iterator.pool)
            super().fit(iterator)

    return Broken


def faulty_reference(make, kwargs: dict, fault: str, precision="float32"):
    """``make`` (the reference's ``make_loss_and_grad``) with ``fault``
    planted in the reference itself (``half_batch`` is the pool's:
    ``half_repeated``)."""
    if fault != "dense_core":
        return make(faulty_kwargs(kwargs, fault), precision, None)
    import jax.numpy as jnp

    def every_key_before(i, chosen):
        T = chosen.shape[0]
        return jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    return make(kwargs, precision, None, select=every_key_before)
