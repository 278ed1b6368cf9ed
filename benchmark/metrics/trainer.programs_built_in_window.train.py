"""Programs JAX had to produce inside the measured window, from disk or
from source. Must be 0: nothing compiles in the window."""


def read(obs):
    return obs.window.builds["programs_built"]
