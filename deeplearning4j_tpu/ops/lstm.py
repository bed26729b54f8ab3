"""Three-variant recurrent engine: fused scan, Pallas persistent cell, scan.

The reference accelerates recurrence through a reflection-loaded helper seam
(LSTMHelpers.java activateHelper/backpropGradientHelper; CudnnLSTMHelper takes
over fwd/bwd when present). The TPU-native equivalent lives here, one module,
three implementations of the same cell math, selected by a calibrated dispatch
gate at trace time (the round-5 ``DL4J_FLASH_MIN_SEQ`` pattern):

* **fused** (variant A, the default): one ``[B, F+H] x [F+H, 4H]`` MXU
  contraction per step — input and recurrent weights concatenated so the scan
  body issues a single matmul instead of two — routed through the
  ``DtypePolicy`` reduction-precision seam (``preferred_element_type``), with
  all four gate activations applied as one vectorized slice-free
  select-on-``[B, 4, H]`` block.
* **pallas** (variant B): a persistent-cell kernel that keeps the whole
  ``[F+H, 4H]`` weight resident in VMEM across a multi-timestep block while
  the Mosaic pipeline double-buffers ``x`` slabs in from HBM, h/c carried in
  revisited VMEM output blocks across the sequential grid. A custom VJP runs
  BPTT as reverse time blocks through the matching backward kernel
  (gates recomputed from the saved h/c histories — flash-attention practice:
  trade FLOPs for HBM). Block size is autotuned over {8, 16, 32} against a
  VMEM-residency budget; see :func:`_vmem_bytes` for the arithmetic.
* **scan** (variant C): the original one-precomputed-input-matmul
  ``lax.scan``, kept as the reference oracle the fast paths are tested
  against (and selectable for on-chip A/B).

Dispatch: ``DL4J_LSTM_IMPL=auto|fused|pallas|scan`` (read at trace time, so
bench A/Bs flip it between traces). ``auto`` engages pallas only past
``(hidden, seq)`` thresholds and under the VMEM budget — the ``batch`` axis
enters through the budget — and takes fused everywhere else, including on
CPU and whenever the cell uses non-tanh/sigmoid activations (the hand-derived
kernel backward is specific to the standard cell); a forced ``pallas`` that
cannot run raises, naming the constraint (:func:`pallas_refusal`). Every
selection increments ``dl4j_lstm_dispatch_total`` and the shared
``dl4j_pallas_dispatch_total`` engagement counter.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from deeplearning4j_tpu.common import accum_dtype, get_policy
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.names import (LSTM_DISPATCH_TOTAL,
                                                    LSTM_PALLAS_BLOCK_STEPS)
from deeplearning4j_tpu.ops.pallas_kernels import (_note_dispatch,
                                                   pallas_unavailable)

Array = jax.Array

#: env knob: force one implementation (auto = calibrated gate)
IMPL_ENV = "DL4J_LSTM_IMPL"
#: pallas block-size candidates (timesteps per grid step)
BLOCK_CHOICES = (32, 16, 8)


def _requested_impl() -> str:
    return os.environ.get(IMPL_ENV, "auto").lower()


def _interpret_default() -> bool:
    """DL4J_LSTM_INTERPRET=1 runs the pallas variant in interpret mode — the
    CPU test hook (layer code has no kwarg path down to the kernel)."""
    return os.environ.get("DL4J_LSTM_INTERPRET") == "1"


def _min_hidden() -> int:
    # uncalibrated default, armed for on-chip capture: below MXU-filling
    # widths the fused scan's single small matmul wins (the kernel's custom
    # call is a fusion barrier, same lesson as DL4J_FLASH_MIN_SEQ)
    return int(os.environ.get("DL4J_LSTM_PALLAS_MIN_HIDDEN", "512"))


def _min_seq() -> int:
    # at least one full minimum-size block of real timesteps, or the
    # kernel's fixed launch cost cannot amortize
    return int(os.environ.get("DL4J_LSTM_PALLAS_MIN_SEQ", "8"))


def _vmem_budget() -> int:
    # the compiler's scoped VMEM default is 16 MiB per core; the rest is
    # headroom for Mosaic's own scratch and for what _vmem_bytes leaves out
    return int(os.environ.get("DL4J_LSTM_VMEM_BUDGET", str(12 * 1024 * 1024)))


def _vmem_bytes(bt: int, batch: int, n_in: int, hidden: int,
                itemsize: int) -> int:
    """Worst-case (backward-kernel) VMEM residency for one block config.

    The backward is the binding constraint: it holds W AND the dW accumulator
    (2x the ``(F+H) x 4H`` weight), streams four double-buffered slabs
    (x, h_prev, c_prev, dy) plus the dx output slab and the lane-padded
    mask column, and carries dh/dc in f32. The forward fits whenever the
    backward does. Checked against the v5e compiler's own scoped-allocation
    figure over 18 (batch, F, H, block, dtype) points: at or above it
    everywhere but one, which it misses by 0.07 MiB — inside the headroom
    :func:`_vmem_budget` keeps under the 16 MiB limit.
    """
    fh4 = (n_in + hidden) * 4 * hidden
    w_and_dw = 2 * fh4 * max(itemsize, 4)  # dW accumulates at least f32
    streams = 2 * bt * batch * (n_in + 3 * hidden + 128) * itemsize
    dx_out = 2 * bt * batch * n_in * itemsize
    carries = 8 * batch * hidden * 4
    work = batch * (n_in + 9 * hidden) * 4  # xh + z + dz tiles in f32
    return w_and_dw + streams + dx_out + carries + work


def _pick_block(seq: int, batch: int, n_in: int, hidden: int,
                dtype) -> Optional[int]:
    """Autotuned timestep-block choice: least padding first, then the larger
    block (better weight-reload amortization per DMA), subject to the VMEM
    budget. Sequences are padded up to a block multiple with zero mask (the
    kernel freezes state on masked steps), so any T is serviceable — the
    budget is the only way this returns None."""
    itemsize = jnp.dtype(dtype).itemsize
    env = os.environ.get("DL4J_LSTM_BLOCK")
    if env:
        bt = int(env)
        ok = bt > 0 and _vmem_bytes(bt, batch, n_in, hidden,
                                    itemsize) <= _vmem_budget()
        return bt if ok else None
    for bt in sorted(BLOCK_CHOICES, key=lambda b: ((-seq) % b, -b)):
        if _vmem_bytes(bt, batch, n_in, hidden, itemsize) <= _vmem_budget():
            return bt
    return None


def pallas_refusal(hidden: int, seq: int, batch: int, n_in: int, *,
                   dtype=None, act_name: Optional[str] = "tanh",
                   gate_name: Optional[str] = "sigmoid",
                   interpret: bool = False) -> Optional[str]:
    """Why the Pallas cell cannot run this call, or None when it can — the
    hard constraints, each read off the device or the shape: a TPU (or a
    test's interpret mode), the standard tanh/sigmoid cell (the kernel
    backward is hand-derived for it), a lane-aligned hidden width on real
    hardware, and a timestep block that fits the VMEM budget."""
    why = None if interpret else pallas_unavailable()
    if why is not None:
        return why
    if act_name not in (None, "tanh") or gate_name not in (None, "sigmoid"):
        return f"cell activations {act_name}/{gate_name} are not tanh/sigmoid"
    if not interpret and hidden % 128:
        return f"hidden {hidden} is not a multiple of the 128-lane tile"
    dtype = dtype if dtype is not None else get_policy().compute_dtype
    if _pick_block(seq, batch, n_in, hidden, dtype) is None:
        itemsize = jnp.dtype(dtype).itemsize
        need = min(_vmem_bytes(bt, batch, n_in, hidden, itemsize)
                   for bt in BLOCK_CHOICES)
        return (f"resident weights and slabs need {need / 2**20:.1f} MiB of "
                f"VMEM at batch {batch}, n_in {n_in}, hidden {hidden}, "
                f"{jnp.dtype(dtype).name}; the budget is "
                f"{_vmem_budget() / 2**20:.0f} MiB")
    return None


def resolve_impl(hidden: int, seq: int, batch: int, n_in: int, *,
                 dtype=None, act_name: str = "tanh",
                 gate_name: str = "sigmoid", impl: Optional[str] = None,
                 interpret: bool = False) -> Tuple[str, Optional[int]]:
    """THE dispatch gate: -> (implementation, pallas block size or None).

    One predicate for every caller (layers, bench, tests) so a forward under
    ``jax.grad`` can never take a different path than the plain forward.
    ``auto`` takes pallas past the ``(hidden, seq)`` thresholds wherever
    :func:`pallas_refusal` has no objection, and fused everywhere else. A
    forced ``pallas`` that the hard constraints refuse is an error naming
    the constraint — a request for the kernel never runs something else."""
    choice = (impl or _requested_impl()).lower()
    if choice not in ("auto", "fused", "pallas", "scan"):
        raise ValueError(f"unknown LSTM impl '{choice}' "
                         "(expected auto|fused|pallas|scan)")
    if choice in ("scan", "fused"):
        return choice, None
    dtype = dtype if dtype is not None else get_policy().compute_dtype
    refusal = pallas_refusal(hidden, seq, batch, n_in, dtype=dtype,
                             act_name=act_name, gate_name=gate_name,
                             interpret=interpret)
    if choice == "pallas" and refusal is not None:
        raise ValueError(f"LSTM impl 'pallas' was requested but cannot run: "
                         f"{refusal}")
    if refusal is None and (choice == "pallas" or (
            hidden >= _min_hidden() and seq >= _min_seq())):
        return "pallas", _pick_block(seq, batch, n_in, hidden, dtype)
    return "fused", None


# ------------------------------------------------------------- dispatch notes
#: counted per TRACE (like dl4j_pallas_dispatch_total): the branch is baked
#: into the compiled program, so each increment is one program embedding the
#: variant choice, and retraces surface as extra counts
_lstm_dispatch = global_registry().counter(
    LSTM_DISPATCH_TOTAL,
    "recurrent-engine variant selections at trace time, by selected "
    "implementation and requested mode")

_pallas_block = global_registry().gauge(
    LSTM_PALLAS_BLOCK_STEPS,
    "timesteps per pallas LSTM kernel block (VMEM-autotuned) at the most "
    "recent pallas trace")


def _note_impl(selected: str, requested: str, bt: Optional[int]) -> None:
    _lstm_dispatch.labels(impl=selected, requested=requested).inc()
    _note_dispatch("lstm_cell", selected == "pallas")
    if bt is not None:
        _pallas_block.set(bt)


# ------------------------------------------------------ variant C: scan oracle
def lstm_scan(params: dict, x: Array, act, gate_act, h0: Array, c0: Array,
              peephole: bool, mask: Optional[Array]):
    """Reference oracle: precomputed input contraction + per-step recurrent
    matmul under lax.scan. x: [B,T,F] -> (outputs [B,T,H], (h, c)).

    Both contractions route ``preferred_element_type`` through the policy's
    grad-accum seam — the per-step ``h @ RW`` included (it used to
    silently accumulate in compute dtype, bypassing the reduction-precision
    policy the big input matmul honored)."""
    pol = get_policy()
    w = params["W"].astype(pol.compute_dtype)
    rw = params["RW"].astype(pol.compute_dtype)
    b = params["b"].astype(pol.compute_dtype)
    adt = accum_dtype(pol.compute_dtype)

    # Input contributions for all timesteps in one big MXU matmul: [B,T,4H];
    # cast straight back so the scan carry dtype below never changes.
    xw = jnp.einsum("btf,fg->btg", x.astype(pol.compute_dtype), w,
                    preferred_element_type=adt
                    ).astype(pol.compute_dtype) + b

    def step(carry, inputs):
        h, c = carry
        xw_t, m_t = inputs
        z = xw_t + jnp.matmul(h.astype(pol.compute_dtype), rw,
                              preferred_element_type=adt
                              ).astype(pol.compute_dtype)
        zi, zf, zg, zo = jnp.split(z.astype(pol.output_dtype), 4, axis=-1)
        if peephole:
            # cast peephole params to the gate dtype: a silent bf16*f32
            # promotion here would flip the scan carry dtype mid-trace
            zi = zi + c * params["pI"].astype(zi.dtype)
            zf = zf + c * params["pF"].astype(zf.dtype)
        i = gate_act(zi)
        f = gate_act(zf)
        g = act(zg)
        c_new = f * c + i * g
        if peephole:
            zo = zo + c_new * params["pO"].astype(zo.dtype)
        o = gate_act(zo)
        h_new = o * act(c_new)
        if m_t is not None:
            m = m_t[:, None]
            h_new = jnp.where(m > 0, h_new, h)
            c_new = jnp.where(m > 0, c_new, c)
        return (h_new, c_new), h_new

    xw_t = jnp.moveaxis(xw, 1, 0)  # [T,B,4H]
    mask_t = jnp.moveaxis(mask, 1, 0) if mask is not None else None
    if mask_t is None:
        (h, c), ys = lax.scan(lambda cr, xi: step(cr, (xi, None)),
                              (h0, c0), xw_t)
    else:
        (h, c), ys = lax.scan(step, (h0, c0), (xw_t, mask_t))
    return jnp.moveaxis(ys, 0, 1), (h, c)


# ------------------------------------------------------ variant A: fused scan
def lstm_fused(params: dict, x: Array, act, gate_act, h0: Array, c0: Array,
               peephole: bool, mask: Optional[Array]):
    """Fused scan: ONE ``[B, F+H] x [F+H, 4H]`` contraction per step (input
    and recurrent weights concatenated once, outside the scan), gate
    activations applied as a single vectorized slice-free block — a
    select over the ``[B, 4, H]`` view instead of four split-then-activate
    chains. Same signature and numerics contract as :func:`lstm_scan`."""
    pol = get_policy()
    cd = pol.compute_dtype
    od = pol.output_dtype
    adt = accum_dtype(cd)
    wcat = jnp.concatenate([params["W"], params["RW"]], axis=0).astype(cd)
    b = params["b"].astype(od)
    B = x.shape[0]
    hidden = params["RW"].shape[0]
    if peephole:
        zeros_h = jnp.zeros_like(params["pI"])
        # rows (pI, pF, 0, 0): the o-gate peephole taps c_new, added after
        # the cell update below
        p_if = jnp.stack([params["pI"], params["pF"], zeros_h, zeros_h]
                         ).astype(od)
        p_o = params["pO"].astype(od)
    # gate 2 (cell candidate) takes `act`; gates 0/1/3 take `gate_act`
    cell_gate = (jnp.arange(4) == 2).reshape(1, 4, 1)

    def step(carry, inputs):
        h, c = carry
        x_t, m_t = inputs
        xh = jnp.concatenate([x_t.astype(cd), h.astype(cd)], axis=-1)
        z = jnp.matmul(xh, wcat, preferred_element_type=adt).astype(od) + b
        z4 = z.reshape(B, 4, hidden)
        if peephole:
            z4 = z4 + c[:, None, :] * p_if
        g4 = jnp.where(cell_gate, act(z4), gate_act(z4))
        i, f, g, o = g4[:, 0], g4[:, 1], g4[:, 2], g4[:, 3]
        c_new = f * c + i * g
        if peephole:
            o = gate_act(z4[:, 3] + c_new * p_o)
        h_new = o * act(c_new)
        if m_t is not None:
            m = m_t[:, None]
            h_new = jnp.where(m > 0, h_new, h)
            c_new = jnp.where(m > 0, c_new, c)
        return (h_new, c_new), h_new

    x_t = jnp.moveaxis(x, 1, 0)  # [T,B,F]
    mask_t = jnp.moveaxis(mask, 1, 0) if mask is not None else None
    if mask_t is None:
        (h, c), ys = lax.scan(lambda cr, xi: step(cr, (xi, None)),
                              (h0, c0), x_t)
    else:
        (h, c), ys = lax.scan(step, (h0, c0), (x_t, mask_t))
    return jnp.moveaxis(ys, 0, 1), (h, c)


# ------------------------------------------- variant B: pallas persistent cell
def _lstm_fwd_kernel(x_ref, w_ref, b_ref, h0_ref, c0_ref, m_ref, *rest,
                     bt: int, hidden: int, peephole: bool):
    """One grid step = ``bt`` timesteps with the full [F+H, 4H] weight
    resident in VMEM (constant index map -> loaded once for the whole
    sequence) while the pipeline double-buffers the next x slab in.

    h/c live in the revisited (B, H) output blocks: initialized from h0/c0
    at program 0, carried across the sequential grid, final state for free.
    """
    if peephole:
        p_ref, ys_ref, cs_ref, h_ref, c_ref = rest
    else:
        ys_ref, cs_ref, h_ref, c_ref = rest
    wd = jnp.promote_types(x_ref.dtype, jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(h_ref.dtype)
        c_ref[...] = c0_ref[...].astype(c_ref.dtype)

    w = w_ref[...].astype(wd)
    b = b_ref[0].astype(wd)
    if peephole:
        p_i = p_ref[0].astype(wd)
        p_f = p_ref[1].astype(wd)
        p_o = p_ref[2].astype(wd)

    def body(t, carry):
        h, c = carry
        x_t = x_ref[t].astype(wd)                         # [B, F]
        m_t = m_ref[t].astype(wd)                         # [B, 1]
        xh = jnp.concatenate([x_t, h], axis=-1)           # [B, F+H]
        z = jnp.dot(xh, w, preferred_element_type=wd) + b  # [B, 4H]
        zi = z[:, :hidden]
        zf = z[:, hidden:2 * hidden]
        zg = z[:, 2 * hidden:3 * hidden]
        zo = z[:, 3 * hidden:]
        if peephole:
            zi = zi + c * p_i
            zf = zf + c * p_f
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = jnp.tanh(zg)
        c_new = f * c + i * g
        if peephole:
            zo = zo + c_new * p_o
        o = jax.nn.sigmoid(zo)
        h_new = o * jnp.tanh(c_new)
        h_new = jnp.where(m_t > 0, h_new, h)
        c_new = jnp.where(m_t > 0, c_new, c)
        ys_ref[t] = h_new.astype(ys_ref.dtype)
        cs_ref[t] = c_new.astype(cs_ref.dtype)
        return h_new, c_new

    h, c = lax.fori_loop(0, bt, body,
                         (h_ref[...].astype(wd), c_ref[...].astype(wd)))
    h_ref[...] = h.astype(h_ref.dtype)
    c_ref[...] = c.astype(c_ref.dtype)


def _lstm_bwd_kernel(x_ref, hp_ref, cp_ref, dy_ref, w_ref, b_ref,
                     dht_ref, dct_ref, m_ref, *rest,
                     bt: int, hidden: int, peephole: bool):
    """Reverse time block: recompute the forward gates from the saved h/c
    histories (no [T, B, 4H] activation stash), then the hand-derived cell
    backward. dW/db/dpeep accumulate in constant-index output blocks; dh/dc
    ride the revisited (B, H) blocks that finish as dh0/dc0, seeded from the
    final-state cotangents at program 0 (TBPTT chunk boundaries hand real
    state cotangents in; plain fit passes zeros)."""
    if peephole:
        p_ref, dx_ref, dw_ref, db_ref, dp_ref, dh_ref, dc_ref = rest
    else:
        dx_ref, dw_ref, db_ref, dp_ref, dh_ref, dc_ref = rest
    wd = jnp.promote_types(x_ref.dtype, jnp.float32)
    n_in = x_ref.shape[-1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
        db_ref[...] = jnp.zeros(db_ref.shape, db_ref.dtype)
        dp_ref[...] = jnp.zeros(dp_ref.shape, dp_ref.dtype)
        dh_ref[...] = dht_ref[...].astype(dh_ref.dtype)
        dc_ref[...] = dct_ref[...].astype(dc_ref.dtype)

    w = w_ref[...].astype(wd)
    b = b_ref[0].astype(wd)
    if peephole:
        p_i = p_ref[0].astype(wd)
        p_f = p_ref[1].astype(wd)
        p_o = p_ref[2].astype(wd)

    def body(j, carry):
        dh, dc = carry
        t = bt - 1 - j
        x_t = x_ref[t].astype(wd)
        hp = hp_ref[t].astype(wd)
        cp = cp_ref[t].astype(wd)
        dy = dy_ref[t].astype(wd)
        m_t = m_ref[t].astype(wd)                         # [B, 1]
        # forward recompute (one extra matmul per step; W is already here)
        xh = jnp.concatenate([x_t, hp], axis=-1)
        z = jnp.dot(xh, w, preferred_element_type=wd) + b
        zi = z[:, :hidden]
        zf = z[:, hidden:2 * hidden]
        zg = z[:, 2 * hidden:3 * hidden]
        zo = z[:, 3 * hidden:]
        if peephole:
            zi = zi + cp * p_i
            zf = zf + cp * p_f
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = jnp.tanh(zg)
        c_new = f * cp + i * g
        if peephole:
            zo = zo + c_new * p_o
        o = jax.nn.sigmoid(zo)
        tc = jnp.tanh(c_new)
        # masked steps froze state in the forward: their gradient passes
        # straight through to t-1 and the gates see zero
        dh_t = dh + dy
        dh_act = jnp.where(m_t > 0, dh_t, 0.0)
        dh_skip = jnp.where(m_t > 0, 0.0, dh_t)
        dc_act = jnp.where(m_t > 0, dc, 0.0)
        dc_skip = jnp.where(m_t > 0, 0.0, dc)
        do = dh_act * tc
        dzo = do * o * (1.0 - o)
        dc_t = dc_act + dh_act * o * (1.0 - tc * tc)
        if peephole:
            dc_t = dc_t + dzo * p_o
        di = dc_t * g
        df = dc_t * cp
        dg = dc_t * i
        dzi = di * i * (1.0 - i)
        dzf = df * f * (1.0 - f)
        dzg = dg * (1.0 - g * g)
        dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)  # [B, 4H]
        dxh = jnp.dot(dz, w.T, preferred_element_type=wd)    # [B, F+H]
        # the weight-sized accumulators live in their VMEM-resident output
        # blocks, never in the loop carry (a (F+H)x4H carry would spill)
        dw_ref[...] += jnp.dot(xh.T, dz, preferred_element_type=wd)
        db_ref[...] += jnp.sum(dz, axis=0, keepdims=True)
        if peephole:
            dp_ref[0:1] += jnp.sum(dzi * cp, axis=0, keepdims=True)
            dp_ref[1:2] += jnp.sum(dzf * cp, axis=0, keepdims=True)
            dp_ref[2:3] += jnp.sum(dzo * c_new, axis=0, keepdims=True)
        dx_ref[t] = dxh[:, :n_in].astype(dx_ref.dtype)
        dh_next = dxh[:, n_in:] + dh_skip
        dc_next = dc_t * f + dc_skip
        if peephole:
            dc_next = dc_next + dzi * p_i + dzf * p_f
        return dh_next, dc_next

    dh, dc = lax.fori_loop(
        0, bt, body, (dh_ref[...].astype(wd), dc_ref[...].astype(wd)))
    dh_ref[...] = dh.astype(dh_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)


def _pallas_forward(x_t, wcat, b2, peep, h0, c0, m_t, bt, peephole,
                    interpret):
    """x_t [T,B,F] time-major, T % bt == 0 -> (ys [T,B,H], cs [T,B,H], h, c).
    cs (per-step cell states) feed the backward's recompute."""
    T, B, F = x_t.shape
    H = h0.shape[-1]
    nb = T // bt
    kernel = functools.partial(_lstm_fwd_kernel, bt=bt, hidden=H,
                               peephole=peephole)
    in_specs = [
        pl.BlockSpec((bt, B, F), lambda i: (i, 0, 0)),
        pl.BlockSpec((F + H, 4 * H), lambda i: (0, 0)),  # resident weights
        pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
        pl.BlockSpec((B, H), lambda i: (0, 0)),
        pl.BlockSpec((B, H), lambda i: (0, 0)),
        pl.BlockSpec((bt, B, 1), lambda i: (i, 0, 0)),
    ]
    operands = [x_t, wcat, b2, h0, c0, m_t]
    if peephole:
        in_specs.append(pl.BlockSpec((3, H), lambda i: (0, 0)))
        operands.append(peep)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bt, B, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((bt, B, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((B, H), lambda i: (0, 0)),  # revisited h carry
            pl.BlockSpec((B, H), lambda i: (0, 0)),  # revisited c carry
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), x_t.dtype),
            jax.ShapeDtypeStruct((T, B, H), x_t.dtype),
            jax.ShapeDtypeStruct((B, H), h0.dtype),
            jax.ShapeDtypeStruct((B, H), c0.dtype),
        ],
        interpret=interpret,
    )(*operands)


def _pallas_backward(x_t, hprev, cprev, wcat, b2, peep, dys, dht, dct, m_t,
                     bt, peephole, interpret):
    T, B, F = x_t.shape
    H = hprev.shape[-1]
    nb = T // bt
    wd = jnp.promote_types(x_t.dtype, jnp.float32)
    kernel = functools.partial(_lstm_bwd_kernel, bt=bt, hidden=H,
                               peephole=peephole)

    def rev3(i):
        return (nb - 1 - i, 0, 0)

    def const2(i):
        return (0, 0)

    in_specs = [
        pl.BlockSpec((bt, B, F), rev3),
        pl.BlockSpec((bt, B, H), rev3),
        pl.BlockSpec((bt, B, H), rev3),
        pl.BlockSpec((bt, B, H), rev3),
        pl.BlockSpec((F + H, 4 * H), const2),
        pl.BlockSpec((1, 4 * H), const2),
        pl.BlockSpec((B, H), const2),
        pl.BlockSpec((B, H), const2),
        pl.BlockSpec((bt, B, 1), rev3),
    ]
    operands = [x_t, hprev, cprev, dys, wcat, b2, dht, dct, m_t]
    if peephole:
        in_specs.append(pl.BlockSpec((3, H), const2))
        operands.append(peep)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bt, B, F), rev3),
            pl.BlockSpec((F + H, 4 * H), const2),
            pl.BlockSpec((1, 4 * H), const2),
            pl.BlockSpec((3, H), const2),
            pl.BlockSpec((B, H), const2),
            pl.BlockSpec((B, H), const2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, F), x_t.dtype),
            jax.ShapeDtypeStruct((F + H, 4 * H), wd),
            jax.ShapeDtypeStruct((1, 4 * H), wd),
            jax.ShapeDtypeStruct((3, H), wd),
            jax.ShapeDtypeStruct((B, H), wd),
            jax.ShapeDtypeStruct((B, H), wd),
        ],
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _pallas_lstm(bt, peephole, interpret, x_t, wcat, b2, peep, h0, c0, m_t):
    ys, _, h, c = _pallas_forward(x_t, wcat, b2, peep, h0, c0, m_t, bt,
                                  peephole, interpret)
    return ys, h, c


def _pallas_lstm_fwd(bt, peephole, interpret, x_t, wcat, b2, peep, h0, c0,
                     m_t):
    ys, cs, h, c = _pallas_forward(x_t, wcat, b2, peep, h0, c0, m_t, bt,
                                   peephole, interpret)
    return (ys, h, c), (x_t, wcat, b2, peep, h0, c0, m_t, ys, cs)


def _pallas_lstm_bwd(bt, peephole, interpret, res, cts):
    x_t, wcat, b2, peep, h0, c0, m_t, ys, cs = res
    dys, dht, dct = cts
    # per-step h_{t-1}/c_{t-1} histories: the saved outputs shifted right by
    # one with the initial state in front
    hprev = jnp.concatenate([h0[None].astype(ys.dtype), ys[:-1]], axis=0)
    cprev = jnp.concatenate([c0[None].astype(cs.dtype), cs[:-1]], axis=0)
    dx, dw, db2, dp, dh0, dc0 = _pallas_backward(
        x_t, hprev, cprev, wcat, b2, peep, dys.astype(x_t.dtype),
        dht.astype(h0.dtype), dct.astype(c0.dtype), m_t, bt, peephole,
        interpret)
    dpeep = dp.astype(peep.dtype) if peephole else jnp.zeros_like(peep)
    return (dx.astype(x_t.dtype), dw.astype(wcat.dtype),
            db2.astype(b2.dtype), dpeep, dh0.astype(h0.dtype),
            dc0.astype(c0.dtype), jnp.zeros_like(m_t))


_pallas_lstm.defvjp(_pallas_lstm_fwd, _pallas_lstm_bwd)


def _lstm_pallas_seq(params: dict, x: Array, h0: Array, c0: Array,
                     peephole: bool, mask: Optional[Array], bt: int,
                     interpret: bool):
    """Engine adapter around the kernel: time-major layout, block padding
    (padded steps carry zero mask, so state freezes and their dx is exactly
    zero), synthesized all-ones mask when the caller has none (``where(1>0)``
    is the identity, so unmasked numerics are untouched)."""
    pol = get_policy()
    cd = pol.compute_dtype
    od = pol.output_dtype
    hidden = params["RW"].shape[0]
    wcat = jnp.concatenate([params["W"], params["RW"]], axis=0).astype(cd)
    b2 = params["b"].astype(cd)[None]
    if peephole:
        peep = jnp.stack([params["pI"], params["pF"], params["pO"]]
                         ).astype(cd)
    else:
        peep = jnp.zeros((3, hidden), cd)
    B, T = x.shape[0], x.shape[1]
    x_t = jnp.moveaxis(x, 1, 0).astype(cd)
    # [T, B, 1]: a step's mask is then one leading-axis read of a [B, 1]
    # column, whatever the dtype's sublane packing
    m_t = (jnp.moveaxis(mask, 1, 0).astype(cd)[:, :, None]
           if mask is not None else jnp.ones((T, B, 1), cd))
    pad = (-T) % bt
    if pad:
        x_t = jnp.concatenate(
            [x_t, jnp.zeros((pad,) + x_t.shape[1:], x_t.dtype)], axis=0)
        m_t = jnp.concatenate([m_t, jnp.zeros((pad, B, 1), m_t.dtype)],
                              axis=0)
    ys, h, c = _pallas_lstm(bt, peephole, interpret, x_t, wcat, b2, peep,
                            h0.astype(cd), c0.astype(cd), m_t)
    return (jnp.moveaxis(ys[:T], 0, 1).astype(od),
            (h.astype(od), c.astype(od)))


# ------------------------------------------------------------------ the seam
def lstm_sequence(params: dict, x: Array, act, gate_act, h0: Array,
                  c0: Array, peephole: bool, mask: Optional[Array], *,
                  act_name: Optional[str] = "tanh",
                  gate_name: Optional[str] = "sigmoid",
                  impl: Optional[str] = None,
                  interpret: Optional[bool] = None):
    """THE recurrent entry point layers call (full sequences, TBPTT chunks,
    and single-step rnnTimeStep alike). Resolves the implementation at trace
    time via :func:`resolve_impl`, notes the dispatch, runs the variant.
    Returns ``(outputs [B,T,H], (h, c))`` like the original scan."""
    if interpret is None:
        interpret = _interpret_default()
    B, T = x.shape[0], x.shape[1]
    hidden = params["RW"].shape[0]
    selected, bt = resolve_impl(hidden, T, B, x.shape[-1],
                                dtype=get_policy().compute_dtype,
                                act_name=act_name, gate_name=gate_name,
                                impl=impl, interpret=interpret)
    _note_impl(selected, impl or _requested_impl(), bt)
    if selected == "scan":
        return lstm_scan(params, x, act, gate_act, h0, c0, peephole, mask)
    if selected == "pallas":
        return _lstm_pallas_seq(params, x, h0, c0, peephole, mask, bt,
                                interpret)
    return lstm_fused(params, x, act, gate_act, h0, c0, peephole, mask)
