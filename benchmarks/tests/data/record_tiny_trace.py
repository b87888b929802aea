import glob, os, shutil, time
import jax, jax.numpy as jnp
def fn(x, i):
    return (x.at[i].add(1.0) @ x).sum()
f = jax.jit(fn)
g = jax.jit(lambda x: jnp.sort(x, axis=0))
x = jnp.ones((256, 256)); i = jnp.arange(16)
f(x, i).block_until_ready(); g(x).block_until_ready()
d = "chiprun_out/tiny_trace"
shutil.rmtree(d, ignore_errors=True)
jax.profiler.start_trace(d)
for _ in range(3):
    f(x, i).block_until_ready()
    time.sleep(0.01)
    g(x).block_until_ready()
jax.profiler.stop_trace()
p = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
shutil.copy(p, "chiprun_out/tiny.xplane.pb")
shutil.rmtree(d)
print("tiny trace", os.path.getsize("chiprun_out/tiny.xplane.pb"), jax.devices())
