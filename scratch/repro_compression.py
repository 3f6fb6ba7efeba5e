import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import ModelConfig, init_params, lm_loss
from repro.train.compression import pod_compressed_value_and_grad

CFG = ModelConfig(name="c", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab_size=512, attn_q_block=32,
                  attn_kv_block=32, loss_seq_chunk=32,
                  param_dtype="float32", compute_dtype="float32",
                  remat="none")
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, 512, (8, 64)), jnp.int32)
batch = {"tokens": toks, "labels": toks,
         "loss_mask": jnp.ones((8, 64), jnp.float32)}
params = init_params(jax.random.PRNGKey(0), CFG)

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))

def loss_fn(p, b):
    return lm_loss(p, b, CFG)[0]

with jax.set_mesh(mesh):
    batch_s = jax.device_put(batch, NamedSharding(mesh, P(("pod", "data"))))
    loss_ref, grads_ref = jax.jit(jax.value_and_grad(loss_fn))(params, batch_s)
    f = pod_compressed_value_and_grad(loss_fn, mesh)
    jf = jax.jit(f)
    loss_c, grads_c = jf(params, batch_s)
    hlo = jf.lower(params, batch_s).compile().as_text()

print("loss", float(loss_ref), float(loss_c))
rels = []
for a, b in zip(jax.tree.leaves(grads_ref), jax.tree.leaves(grads_c)):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    rels.append(np.abs(a - b).max() / (np.abs(a).max() + 1e-12))
print("max rel err", max(rels))
print("s8", "s8[" in hlo, "all-gather", "all-gather" in hlo)
print("COMPRESSION_OK")
