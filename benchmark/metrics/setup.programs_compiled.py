"""Programs built from source during set-up (produced and not served from
the persistent cache), from the jax.monitoring listener. After a cell's
first run in a checkout it should read 0."""


def read(obs):
    return obs.setup_builds["programs_compiled"]
