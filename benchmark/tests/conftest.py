import jax

# the tests compile tiny programs over and over: keep them out of the
# persistent cache (and its warnings on every hit out of the test log)
jax.config.update("jax_enable_compilation_cache", False)
