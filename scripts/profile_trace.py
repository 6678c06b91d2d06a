"""Device-op profile of the scanned GossipSub step (bench configuration).

Thin CLI over go_libp2p_pubsub_tpu/perf/profile.py — the library-ified
profiler that captures a jax.profiler trace of one scanned segment and
prints the top HLO ops by self time (the attribution an ablation timer
cannot give: per-call dispatch time swamps isolated-phase timings).

Builds the EXACT bench workload (perf.sweep.build_bench) so op
attribution maps 1:1 onto what BENCH_r*.json measures; BENCH_CONFIG
selects the variant, BENCH_PHASE_R the cadence (the bench default is
r=8; BENCH_PHASE_R=1 profiles the per-round step).

Usage: python scripts/profile_trace.py [N] [ROUNDS]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from go_libp2p_pubsub_tpu.perf.profile import main  # noqa: E402

if __name__ == "__main__":
    main()
