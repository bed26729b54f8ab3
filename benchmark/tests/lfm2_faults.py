"""Faults of a cell whose mixers are gated short convolutions, planted in the
PROGRAM under the cell's own driver (``broken``, for ``drive_lfm2_faults.py``
and the tests); the reference follows the sound configuration and pool.

* ``taps_reversed``: the filter reads ahead, ``Z_t = sum_j w_j V_{t+j}``
  (the last T outputs of the padded correlation, not the first).
* ``no_cg_gate``: ``A = Z W_out``, the ``Cg`` gate left out.
* ``no_bias_step``: the routers' bias never moves.
* ``half_batch``: half of the single sequence repeats the other half
  (``keye_faults.half_repeated``).
"""
import importlib

from keye_faults import half_repeated

FAULTS = ("taps_reversed", "no_cg_gate", "no_bias_step", "half_batch")


def faulty_conv(fault: str):
    """``decoder.short_conv``'s place with ``fault`` planted."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers import decoder

    def conv(u, w_in, conv_w, w_out):
        bcx = decoder._mm(u, w_in)
        b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
        v, T, L = b * x, u.shape[1], conv_w.shape[0]
        ahead = fault == "taps_reversed"
        pad = ((0, 0), (0, L - 1) if ahead else (L - 1, 0), (0, 0))
        vp = jnp.pad(v, pad)
        z = sum(conv_w[j] * vp[:, j:j + T] for j in range(L))
        y = z if fault == "no_cg_gate" else c * z
        return decoder._mm(y.astype(bcx.dtype), w_out)

    return conv


def broken(cell: dict, fault: str):
    """The cell's driver with ``fault`` planted in the program's path."""
    drivers = importlib.import_module("drivers." + cell["traffic"]["driver"])

    class Broken(drivers.Driver):
        def build(self, weights):
            sound = self.kwargs
            if fault == "no_bias_step":
                self.kwargs = dict(sound, load_balance_coeff=0.0)
            elif fault in ("taps_reversed", "no_cg_gate"):
                from deeplearning4j_tpu.nn.conf.layers import decoder

                # the step program is traced at the first dispatch, long
                # after build: the patch stays for the process
                decoder.short_conv = faulty_conv(fault)
            try:
                return super().build(weights)
            finally:
                self.kwargs = sound

        def fit(self, iterator):
            if fault == "half_batch":
                iterator.pool = half_repeated(iterator.pool)
            super().fit(iterator)

    return Broken
