"""Outputs and gradients of a tiny Trinity-Mini block and a tiny
DeepSeek-V2-Lite block (DecoderBlock at its defaults but for the fields the
two builders set), float32 on the CPU: run on the parent's tree to record
tests/golden/decoder_blocks_pr34.npz, and by tests/test_keye_vl2.py on this
tree to compare, bit for bit."""
import sys
import jax, jax.numpy as jnp, numpy as np
from deeplearning4j_tpu import common
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock

BLOCKS = {
    "trinity": dict(n_in=32, n_out=32, norm_eps=1e-5, norm_placement="sandwich",
                    attention="gqa", n_heads=4, n_kv_heads=2, head_dim=8, window=6,
                    rope_theta=10000.0, ffn="moe", router="sigmoid_bias", n_experts=16,
                    experts_per_token=3, expert_hidden=16, shared_hidden=16,
                    experts_held=[4, 8], route_scale=2.826, bias_update_rate=0.001),
    "deepseek": dict(n_in=32, n_out=32, attention="mla", n_heads=4, kv_rank=16,
                     qk_nope_dim=8, qk_rope_dim=4, v_dim=8, rope_theta=10000.0,
                     ffn="moe", router="softmax", n_experts=16, experts_per_token=3,
                     expert_hidden=16, shared_hidden=32, experts_held=[0, 4],
                     aux_loss_weight=0.001),
}

def run():
    out = {}
    with common.override_policy("float32"):
        for name, kw in BLOCKS.items():
            layer = DecoderBlock(weight_init="xavier", **kw)
            itype = InputType.recurrent(32, 16)
            params = layer.init_params(jax.random.PRNGKey(7), itype)
            state = layer.init_state(itype)
            x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 32))
            def f(p, xx):
                y, st = layer.apply(p, state, xx, train=True)
                return jnp.sum(jnp.sin(y)) + sum(
                    jnp.sum(v) for k, v in sorted(st.items()) if v.dtype == jnp.float32), (y, st)
            (_, (y, st)), (gp, gx) = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(params, x)
            out[f"{name}/y"] = np.asarray(y); out[f"{name}/dx"] = np.asarray(gx)
            for k, v in sorted(st.items()): out[f"{name}/state/{k}"] = np.asarray(v)
            for k, v in sorted(gp.items()): out[f"{name}/d/{k}"] = np.asarray(v)
            for k, v in sorted(params.items()): out[f"{name}/p/{k}"] = np.asarray(v)
    return out

if __name__ == "__main__":
    np.savez(sys.argv[1], **run())
    print(len(run()), "arrays")
