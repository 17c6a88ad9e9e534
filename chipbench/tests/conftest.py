"""The benchmark's own tests run on CPUs, with the Pallas kernels in the
interpreter, whatever the caller's environment says."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
