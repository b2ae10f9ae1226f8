"""Seeded collective-trainer fixture for the elastic chaos drills.

One rank of a deterministic data-parallel linear-regression run:

- replicated state: weights ``w`` + momentum ``v`` (identical on all
  ranks — every rank computes the same "allreduced" update from the
  full schedule, simulating lock-step dp);
- sharded state: matrix ``M`` (ROWS x 3), axis-0 partitioned across
  the world; each owned row accumulates ``(row_id + 1) * loss`` per
  step, so any resharding bug shows up as wrong VALUES, not just
  wrong shapes;
- per-rank state: this rank's RNG step counter.

Sample order comes from cluster_ckpt.SampleSchedule (counter-based
Philox), checkpoints from ClusterCheckpoint on an every-N-steps
cadence, heartbeats + deterministic kill/stall injection from
elastic.note_step. Per-step jsonl records (loss + wall time) let the
drill compare a faulted run's loss curve against the fault-free one
and measure detect→resume latency.

Env contract (beyond the launcher's PADDLE_* cluster env):
  ELASTIC_DRILL_OUT         output dir (jsonl / npz / arming markers)
  ELASTIC_DRILL_STEPS       total steps (default 12)
  ELASTIC_DRILL_SAVE_EVERY  checkpoint cadence (default 2)
  ELASTIC_DRILL_STEP_SLEEP  seconds per step (default 0.05)
  ELASTIC_DRILL_KILL_RANK   rank to kill ONCE (first life only)
  ELASTIC_DRILL_FLAP_RANK   rank to kill EVERY life (crash loop /
                            exclusion drills)
  ELASTIC_DRILL_KILL_AT     step number the kill fires at
  ELASTIC_DRILL_STALL_RANK / ELASTIC_DRILL_STALL  hang one rank at
                            ELASTIC_DRILL_KILL_AT for N seconds
"""
import json
import os
import sys
import time

import numpy as np

RANK = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
WORLD = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
OUT = os.environ["ELASTIC_DRILL_OUT"]
ROOT = os.environ["PADDLE_TPU_CLUSTER_CKPT_DIR"]
STEPS = int(os.environ.get("ELASTIC_DRILL_STEPS", "12"))
SAVE_EVERY = int(os.environ.get("ELASTIC_DRILL_SAVE_EVERY", "2"))
STEP_SLEEP = float(os.environ.get("ELASTIC_DRILL_STEP_SLEEP", "0.05"))
KILL_RANK = int(os.environ.get("ELASTIC_DRILL_KILL_RANK", "-1"))
FLAP_RANK = int(os.environ.get("ELASTIC_DRILL_FLAP_RANK", "-1"))
KILL_AT = os.environ.get("ELASTIC_DRILL_KILL_AT", "")
STALL_RANK = int(os.environ.get("ELASTIC_DRILL_STALL_RANK", "-1"))
STALL = os.environ.get("ELASTIC_DRILL_STALL", "")

os.makedirs(OUT, exist_ok=True)

# arm the deterministic faults BEFORE the injector's first use:
# KILL_RANK dies once (marker file remembers the spent life across
# restarts — the launcher re-runs us with the same env), FLAP_RANK
# dies every life
arm_kill = False
if KILL_AT:
    if RANK == FLAP_RANK:
        arm_kill = True
    elif RANK == KILL_RANK:
        marker = os.path.join(OUT, "kill_spent")
        if not os.path.exists(marker):
            with open(marker, "w"):
                pass
            arm_kill = True
if arm_kill:
    os.environ["PADDLE_PS_FAULT_KILL_AT_STEP"] = KILL_AT
else:
    os.environ.pop("PADDLE_PS_FAULT_KILL_AT_STEP", None)
arm_stall = False
if STALL and RANK == STALL_RANK:
    marker = os.path.join(OUT, "stall_spent")   # first life only
    if not os.path.exists(marker):
        with open(marker, "w"):
            pass
        arm_stall = True
if arm_stall:
    os.environ["PADDLE_PS_FAULT_STALL"] = STALL
    os.environ["PADDLE_PS_FAULT_STALL_POINT"] = "trainer_step"
else:
    for _k in ("PADDLE_PS_FAULT_STALL", "PADDLE_PS_FAULT_STALL_POINT"):
        os.environ.pop(_k, None)

from paddle_tpu.distributed import elastic  # noqa: E402
from paddle_tpu.distributed.cluster_ckpt import (  # noqa: E402
    ClusterCheckpoint, SampleSchedule)

SEED, N, G, DIM, ROWS = 7, 256, 8, 4, 24

rs = np.random.RandomState(SEED)
X = rs.randn(N, DIM)
w_true = np.arange(1.0, DIM + 1)
y = X @ w_true

sched = SampleSchedule(seed=SEED, epoch=0, num_samples=N,
                       global_batch=G)
# rank 0's merge waits for every rank's part. In a life with a fault to
# find, a short wait keeps rank 0 stepping beside a rank that will never
# write; in a fault-free life the only thing it can wait for is a rank
# the machine's load has held back (four interpreters starting beside a
# busy test run are seconds apart), and giving up on one fails the life
ck = ClusterCheckpoint(ROOT, rank=RANK, world=WORLD,
                       every_steps=SAVE_EVERY,
                       merge_timeout=5.0 if (KILL_AT or STALL) else 60.0)

base, rem = divmod(ROWS, WORLD)
row_lo = RANK * base + min(RANK, rem)
row_hi = row_lo + base + (1 if RANK < rem else 0)
my_rows = np.arange(row_lo, row_hi)

w = np.zeros(DIM)
v = np.zeros(DIM)
M = np.zeros((len(my_rows), 3))
start = 0
if ClusterCheckpoint.exists(ROOT):
    state, info = ck.restore()
    w, v, M = state["w"], state["v"], state["M"]
    start = info["step"] + 1
    assert M.shape[0] == len(my_rows), \
        f"reshard: got {M.shape[0]} rows, own {len(my_rows)}"

elastic.start_heartbeat(interval=0.1)

# the first collective of a real gang: no rank steps before every rank has
# restored. Without it nothing here holds the ranks together (each
# recomputes the "allreduce" alone), and a rank that the machine's load
# started late finds the others steps ahead: rank 0's restore purges the
# parts past the committed step, a faster rank's fresh one among them, and
# the merge of that step then waits for a part that will not come. Keyed by
# where this life starts: a life that committed nothing since the last one
# meets that one's files and passes as it did before there was a barrier.
arrived = os.path.join(OUT, f"restored_s{start}_w{WORLD}_r%d")
with open(arrived % RANK, "w"):
    pass
deadline = time.monotonic() + 60.0
while not all(os.path.exists(arrived % r) for r in range(WORLD)):
    if time.monotonic() >= deadline:
        sys.exit(f"rank {RANK}: not every rank of {WORLD} restored")
    time.sleep(0.01)

losses = open(os.path.join(OUT, f"loss_rank{RANK}.jsonl"), "a")

for step in range(start, STEPS):
    elastic.note_step(step)  # heartbeat progress + fault hooks
    g_idx = sched.global_indices(step)
    per = G // WORLD
    # lock-step dp: every rank computes the same mean-of-rank-means
    # reduction (the world-dependent summation ORDER is honest — a
    # resize moves the loss curve only within fp tolerance)
    grad = np.zeros(DIM)
    loss = 0.0
    for r in range(WORLD):
        sl = g_idx[r * per:(r + 1) * per]
        err = X[sl] @ w - y[sl]
        grad += X[sl].T @ err / per
        loss += float(np.mean(err ** 2))
    grad /= WORLD
    loss /= WORLD
    v = 0.9 * v + grad
    w = w - 0.05 * v
    M += (my_rows[:, None] + 1) * loss
    losses.write(json.dumps({"step": step, "loss": loss,
                             "world": WORLD, "rank": RANK,
                             "t": time.time()}) + "\n")
    losses.flush()
    os.fsync(losses.fileno())
    ck.maybe_save(step, replicated={"w": w, "v": v},
                  sharded={"M": M},
                  per_rank={"rng": np.array([step], np.int64)},
                  extra_meta={"loss": loss})
    if STEP_SLEEP:
        time.sleep(STEP_SLEEP)

ck.wait()
np.savez(os.path.join(OUT, f"final_rank{RANK}.npz"),
         w=w, v=v, M=M, rows=my_rows)
losses.close()
print(f"TRAINER {RANK}/{WORLD} DONE", flush=True)
sys.exit(0)
