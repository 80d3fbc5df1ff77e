"""apex_tpu packaging.

Mirrors the reference's two-tier install (setup.py feature flags,
SURVEY.md §1): a plain install is pure-Python-functional; the native runtime
(`apex_tpu/csrc`) is built lazily at first use with g++ (no build-time
extension needed), or ahead of time via ``python setup.py build_native``.
"""

import os
import subprocess

from setuptools import Command, find_packages, setup


class BuildNative(Command):
    description = "build the C++ runtime (.so) ahead of time"
    user_options = []

    def initialize_options(self):
        pass

    def finalize_options(self):
        pass

    def run(self):
        from apex_tpu import native
        native._load()
        print("native runtime available:", native.available)


setup(
    name="apex_tpu",
    version="0.1.0",
    description="TPU-native mixed-precision & distributed training framework "
                "(the capabilities of NVIDIA Apex, rebuilt on jax/XLA/Pallas)",
    packages=find_packages(include=["apex_tpu", "apex_tpu.*"]),
    package_data={"apex_tpu": ["csrc/*.cpp"]},
    python_requires=">=3.12",
    # The one installation the code is written for and has run on (the
    # jax < 0.9 compatibility branches are gone: jax.shard_map,
    # lax.axis_size, lax.pcast, ShapeDtypeStruct(vma=)).  The TPU runtime
    # that ran chip_smoke.py is libtpu 0.0.34.
    install_requires=["jax>=0.9.0", "jaxlib>=0.9.0", "flax>=0.12.3",
                      "numpy>=2.0"],
    extras_require={"tpu": ["libtpu>=0.0.34"]},
    cmdclass={"build_native": BuildNative},
)
