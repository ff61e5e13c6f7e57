"""Distribution layer: sharding rules, constraint wrappers, sharded DFL steps.

Three modules:

  * `sharding`    — divisibility-aware PartitionSpec inference over the
                    ("data", "model") mesh (plus the optional "pod" node axis).
  * `constraints` — `with_sharding_constraint` wrappers used inside model
                    forward passes; no-ops when no mesh is active, so the same
                    model code runs on a bare CPU and on the production mesh.
  * `dfl_step`    — the jit-able steps: the single-replica train step and the
                    multi-pod DFL round with DecDiff gossip over the node axis.
"""
from repro.dist import constraints, dfl_step, sharding  # noqa: F401
