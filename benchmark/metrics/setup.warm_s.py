"""The warm-up rounds, compilation or cache loads included, up to the
start of the measured window."""


def read(obs):
    return obs.phases["setup.warm_s"]
