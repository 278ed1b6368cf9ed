"""The program's own set-up: corpus, dictionary, mv.init, tables, trainer."""


def read(obs):
    return obs.phases["setup.build_s"]
