# Tests run on the single host CPU device (tools that need more devices
# force them via XLA_FLAGS in their own process).  Keep jax state
# untouched here.
import jax

jax.config.update("jax_platform_name", "cpu")
