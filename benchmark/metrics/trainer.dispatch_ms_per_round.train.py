"""Milliseconds a block the trainer's own thread spent in its own dispatches
(Dashboard TRAINER_BLOCK_UPLOAD + TRAINER_BLOCK_IDS + TRAINER_BLOCK_STEP +
TRAINER_BLOCK_LOSS of the PS trainer's loop, TRAINER_GROUP_DISPATCH of the
local trainer's, over the window's blocks; measured window, profiler off). With
`client.issue_ms_per_round.train` and `client.wait_ms.train` it accounts
for `trainer.block_ms.train`: what is left is the loop's unnamed rest."""

from benchmark.lib import callerspans


MONITORS = ('TRAINER_BLOCK_UPLOAD', 'TRAINER_BLOCK_IDS', 'TRAINER_BLOCK_STEP',
            'TRAINER_BLOCK_LOSS', 'TRAINER_GROUP_DISPATCH')


def read(obs):
    return callerspans.ms_per_round(obs, MONITORS)
