"""Window seconds over blocks (one block = one step of
`centers_per_block` centers), harness clock closed by a device sync."""


def read(obs):
    if not obs.window.rounds:
        return None
    return obs.window.seconds * 1e3 / obs.window.rounds
