"""Package build for horovod_tpu.

Reference: /root/reference/setup.py builds three CMake native extensions;
here the native runtime (horovod_tpu/_native/hvd C++ core) builds as a
plain shared library loaded via ctypes — `make` driven lazily by
horovod_tpu/_native/__init__.py:build() — so `pip
install -e .` needs no compiler until the eager multi-process runtime is
first used (and the pure-Python/XLA path never needs it).
"""

from setuptools import find_packages, setup

setup(
    name="horovod_tpu",
    version="0.1.0",
    description=(
        "TPU-native distributed deep-learning training framework "
        "(Horovod-capability rebuild on JAX/XLA/Pallas)"
    ),
    packages=find_packages(include=["horovod_tpu*"]),
    python_requires=">=3.9",
    install_requires=["jax", "flax", "optax", "numpy"],
    entry_points={
        "console_scripts": [
            "hvdrun = horovod_tpu.runner.launch:main",
            "horovodrun_tpu = horovod_tpu.runner.launch:main",
        ]
    },
)
