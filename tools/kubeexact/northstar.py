"""The committed north-star environment: the deployment shape every
exactness bound is evaluated at.

The prover traces each program once at a small probe rung; every symbolic
bound it derives is then evaluated under THIS environment — the largest
shape the roadmap commits to serving (100k committed pods x 10k nodes,
rescore chunks of 4096 pending pods, max zone/resource vocabularies, the
largest mesh any deployment profile uses).  The environment is committed
into EXACT_MANIFEST.json, so growing the deployment target is an explicit,
reviewed change that re-runs the headroom audit.

No jax imports here: ``--check`` (the committed-manifest gate) must run in
environments without jax, exactly like tools/kubeaot.
"""

from __future__ import annotations

# f32 integer-exactness ceiling (see bounds.INT_EXACT_LIMIT; duplicated
# here as a plain literal so --check needs no other imports)
INT_EXACT_LIMIT = float(2 ** 24)

# Every proved float sum must clear its north-star bound by at least this
# factor — room for one more doubling of the dominating axis plus slack
# for per-shard padding before the invariant is threatened.
MARGIN_FLOOR = 4.0

# dimension symbols: probe-rung dim sizes are mapped to these names by
# the driver (bounds.sym_table) and bounds re-evaluate here.
#   B  pending-pod batch bucket      (rescore chunk 4096)
#   N  node-slot bucket              (10240 nodes -> pow2 16384)
#   P  committed-pod bucket          (100k existing pods -> pow2 131072)
#   R  resource-channel ceiling
#   Z  zone-vocabulary ceiling
#   MESH:pods / MESH:nodes           largest per-axis mesh fan any
#                                    profile uses (v5e-8 pod-axis 8x;
#                                    (2,4)/(4,2) node-axis up to 4)
NORTHSTAR_ENV = {
    "B": 4096.0,
    "N": 16384.0,
    "P": 131072.0,
    "R": 16.0,
    "Z": 64.0,
    "MESH:pods": 8.0,
    "MESH:nodes": 4.0,
}
