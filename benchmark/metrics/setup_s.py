"""Process start to the start of the measured window, compilation
included, less `setup.attach_s`: interpreter start and imports, the
program's own set-up, and the warm-up (harness clock).

The attach (the runtime's first `jax.devices()`) is taken out because it
is the runtime's and not this repo's: 7.2 to 11.6 s from one machine to
the next and drifting by over a second across back-to-back runs on one
(my chip runs, PR 23), whatever the harness or the program does. It
stays reported as `setup.attach_s`.
"""


def read(obs):
    return sum(seconds for phase, seconds in obs.phases.items()
               if phase != "setup.attach_s")
