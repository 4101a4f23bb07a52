"""Stage-by-stage timing probe for the precompute path on the device."""

import sys
import time

t0 = time.time()


def mark(s):
    print(f"[{time.time() - t0:7.1f}s] {s}", file=sys.stderr, flush=True)


import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# the compile cache is the one cometbft_tpu/ops/__init__.py sets
mark(f"jax imported; devices: {jax.devices()}")

import numpy as np  # noqa: E402

from cometbft_tpu.crypto import ed25519 as ed  # noqa: E402
from cometbft_tpu.ops import precompute as PR  # noqa: E402

mark("precompute imported")
nval = int(os.environ.get("KB_NVAL", 150))
privs = [ed.gen_priv_key() for _ in range(nval)]
pubs = [p.pub_key().bytes() for p in privs]
mark(f"{nval} keys generated")
tbl = PR.b_comb8()
mark(f"b_comb8 host build done shape={tbl.shape}")
entry = PR.TABLE_CACHE.lookup_or_build(pubs)
mark(f"table build dispatched wb={entry.window_bits} "
     f"bytes={entry.nbytes / 1e6:.0f}MB")
v = np.asarray(entry.valid)
mark(f"valid fetched: {v.all()}")
tb = np.asarray(jax.device_get(entry.table[0, 0, :4]))
mark("table sample fetched (build complete)")
