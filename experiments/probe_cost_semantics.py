import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
N = 512

def plain(x, w):
    return x @ w

def scanned(x, ws):
    def body(c, w):
        return c @ w, None
    y, _ = jax.lax.scan(body, x, ws)
    return y

with mesh:
    xs = jax.ShapeDtypeStruct((N, N), jnp.float32)
    ws = jax.ShapeDtypeStruct((N, N), jnp.float32)
    wss = jax.ShapeDtypeStruct((10, N, N), jnp.float32)
    sh = NamedSharding(mesh, P("data", "model"))
    c1 = jax.jit(plain, in_shardings=(sh, None)).lower(xs, ws).compile()
    c2 = jax.jit(scanned, in_shardings=(sh, None)).lower(xs, wss).compile()
    f1 = c1.cost_analysis()["flops"]
    f2 = c2.cost_analysis()["flops"]
    print("plain flops:", f1, "expected/dev:", 2 * N**3 / 8)
    print("scan x10 flops:", f2, "ratio scan/plain:", f2 / f1)
    print("plain bytes:", c1.cost_analysis()["bytes accessed"])
    print("scan bytes:", c2.cost_analysis()["bytes accessed"])
    m2 = c2.memory_analysis()
    print("scan temp bytes:", m2.temp_size_in_bytes,
          "arg:", m2.argument_size_in_bytes)
