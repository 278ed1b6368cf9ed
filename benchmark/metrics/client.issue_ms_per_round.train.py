"""Milliseconds a block (a step) the trainer's thread spent issuing its
table requests (Dashboard CLIENT_ISSUE_GET + CLIENT_ISSUE_ADD over the
window's rounds: each public async entry, checks and blobs to the message
in the worker actor's mailbox; measured window, profiler off)."""

from benchmark.lib import callerspans


MONITORS = ('CLIENT_ISSUE_GET', 'CLIENT_ISSUE_ADD')


def read(obs):
    return callerspans.ms_per_round(obs, MONITORS)
