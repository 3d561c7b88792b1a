from setuptools import find_packages, setup

setup(
    name="shallowspeed_tpu",
    version="0.1.0",
    description="TPU-native distributed-training framework (DP x PP on a JAX mesh)",
    packages=find_packages(include=["shallowspeed_tpu", "shallowspeed_tpu.*"]),
    python_requires=">=3.10",
    # written and tested against exactly one installation: jax/jaxlib 0.9.0
    # (libtpu 0.0.34 on the chip); there are no version branches in the code
    install_requires=["jax>=0.9.0", "numpy"],
)
