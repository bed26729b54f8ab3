"""Faults of a cell whose mixers are Mamba-2 state-space scans, planted in the
PROGRAM under the cell's own driver (``broken``, for
``drive_nemotron_faults.py`` and the tests); the reference follows the sound
configuration and pool.

* ``no_decay``: the state never decays, ``exp(dt A)`` -> 1 (``A`` = 0).
* ``no_carry``: the state is not carried from chunk to chunk: every chunk of
  the scan starts from 0.
* ``no_z_gate``: ``y`` is normed without the ``silu(z)`` gate.
* ``relu_unsquared``: every expert, routed and shared, ``relu(u Wu) Wd``.
* ``no_bias_step``: the routers' bias never moves.
* ``half_batch``: half of the single sequence repeats the other half
  (``keye_faults.half_repeated``).
"""
import importlib
import types

from keye_faults import half_repeated

FAULTS = ("no_decay", "no_carry", "no_z_gate", "relu_unsquared",
          "no_bias_step", "half_batch")


def faulty_scan(fault: str):
    """``ssd.ssd_scan``'s place with ``fault`` planted."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import ssd

    sound = ssd.ssd_scan

    def scan(x, dt, A, B, C, D, chunk):
        if fault == "no_decay":
            return sound(x, dt, A * 0, B, C, D, chunk)
        # every chunk its own sequence, so nothing crosses a chunk's edge
        Bt, T = x.shape[:2]
        pad = -T % chunk
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
        cut = lambda a: a.reshape(-1, chunk, *a.shape[2:])
        y = sound(cut(x), cut(dt), A, cut(B), cut(C), D, chunk)
        return y.reshape(Bt, T + pad, *y.shape[2:])[:, :T]

    return scan


def ungated(sound):
    """``DecoderBlock._mamba_group`` with the ``silu(z)`` gate left out: ``z``
    a constant whose ``silu`` the group's norm divides out again."""
    import jax.numpy as jnp

    def group(block, args):
        return sound(block, (jnp.full_like(args[0], 1e4),) + tuple(args[1:]))

    return group


def relu_unsquared():
    """``jax.nn.relu`` in ``moe.py``'s and ``decoder.py``'s hands replaced by
    ``sqrt(relu)``, so that their squares are the plain ReLU."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers import decoder, moe

    nn = types.SimpleNamespace(**{**vars(jax.nn), "relu": lambda v: jnp.sqrt(
        jnp.maximum(v, 1e-30))})
    shim = types.SimpleNamespace(**{**vars(jax), "nn": nn})
    moe.jax = decoder.jax = shim


def broken(cell: dict, fault: str):
    """The cell's driver with ``fault`` planted in the program's path."""
    drivers = importlib.import_module("drivers." + cell["traffic"]["driver"])

    class Broken(drivers.Driver):
        def build(self, weights):
            sound = self.kwargs
            from deeplearning4j_tpu.nn.conf.layers import decoder
            from deeplearning4j_tpu.ops import ssd

            # the step program is traced at the first dispatch, long after
            # build: each patch stays for the process
            if fault == "no_bias_step":
                self.kwargs = dict(sound, load_balance_coeff=0.0)
            elif fault in ("no_decay", "no_carry"):
                ssd.ssd_scan = faulty_scan(fault)
            elif fault == "no_z_gate":
                decoder.DecoderBlock._mamba_group = ungated(
                    decoder.DecoderBlock._mamba_group)
            elif fault == "relu_unsquared":
                relu_unsquared()
            try:
                return super().build(weights)
            finally:
                self.kwargs = sound

        def fit(self, iterator):
            if fault == "half_batch":
                iterator.pool = half_repeated(iterator.pool)
            super().fit(iterator)

    return Broken
