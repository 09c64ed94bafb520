"""The benchmark's own tests run on the CPU at tiny sizes."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
