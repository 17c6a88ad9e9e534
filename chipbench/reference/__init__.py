"""Plain references: straightforward jax.numpy, float32, no kernels, no
cache, no batching tricks.  Nothing here imports the program under test."""
