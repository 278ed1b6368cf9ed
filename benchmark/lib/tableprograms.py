"""The table's device programs by the stems their hashed names leave
(`jit__lambda_4628...` -> `jit__lambda`). The metrics of PR 23 key on
these; since PR 24 the programs' operations also carry `jax.named_scope`
names (`mv.table.gather`, `mv.update.scatter_add`, ...), which
`xplane.reduce` gives as `scopes` and newer metrics read:

- ``jit__lambda``      MatrixServer._gather: the row gather of a Get
- ``jit_rows_padded``  UpdateEngine's rows program: the scatter-add of an Add
- ``jit_group``        DeviceCorpusTrainer's group program: gather, SGNS step
                       and scatter-add of `steps_per_dispatch` blocks in one
"""

GATHER = "jit__lambda"
SCATTER_ADD = "jit_rows_padded"
LOCAL_GROUP = "jit_group"


def seconds(trace: dict, stems) -> float:
    return sum(trace["programs"].get(s, {}).get("seconds", 0.0)
               for s in stems)

