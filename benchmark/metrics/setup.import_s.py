"""Interpreter start and the imports (jax, then multiverso_tpu and the
harness), the attach taken out."""


def read(obs):
    return obs.phases["setup.import_s"]
