import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_NO_COMPILE_CACHE", "1")
