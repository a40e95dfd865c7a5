"""Plain float32 `jax.numpy` references of the models the system trains:
what the tests and the chip comparisons hold the Gluon path to."""
