"""Corpus tokens the trainer consumed in the window (the reference's word
count: subsampled-away tokens included) over the window's seconds. The
window ends at the first block boundary after --seconds and is closed
by a forced device sync."""


def read(obs):
    words = obs.window.work.get("words")
    return None if words is None else words / obs.window.seconds
