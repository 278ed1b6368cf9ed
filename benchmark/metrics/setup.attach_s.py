"""The harness's own first jax.devices() call, made after `import jax` and
before anything of multiverso_tpu is imported or called."""


def read(obs):
    return obs.phases["setup.attach_s"]
