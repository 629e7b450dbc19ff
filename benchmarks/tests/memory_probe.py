"""By hand, on the chip: what `memory_stats()` counts. A jitted program with a
2 GiB temporary, the stats before and after."""
import json

import jax
import jax.numpy as jnp

d = jax.devices()[0]
print("before", json.dumps(d.memory_stats()))
x = jnp.ones((1024, 1024), jnp.float32)


@jax.jit
def f(x):
    big = jnp.broadcast_to(x[None], (512, 1024, 1024)) * 2.0  # 2 GiB f32
    big = jnp.cumsum(big, axis=0)
    return big[-1].sum()


lowered = f.lower(x).compile()
print("memory_analysis temp", lowered.memory_analysis().temp_size_in_bytes)
print(float(f(x)))
print("after", json.dumps(d.memory_stats()))
y = jnp.ones((256, 1024, 1024), jnp.float32) + 1
y.block_until_ready()
print("after 1 GiB live array", json.dumps(d.memory_stats()))
