"""Online-serving example: feature store as the low-latency request plane.

    PYTHONPATH=src python examples/online_serving.py --requests 8 --new-tokens 16

Each request names a session (entity id); the ONLINE store serves the
session's latest materialized context through the device GET (a search of
the resident key index and a row gather), the model prefills it and decodes
new tokens with a KV cache.  Thin veneer over
repro.launch.serve (the production driver), plus a skew check: the served
online context must equal the offline store's latest record for the same id
(the paper's central online/offline-consistency promise).
"""

import sys

import numpy as np

from repro.launch import serve


def main():
    argv = sys.argv[1:] or ["--requests", "8", "--new-tokens", "16"]
    out = serve.main(argv)
    assert out["tokens_generated"] > 0
    print(
        f"\nexample complete: {out['context_hits']}/{out['requests']} sessions "
        f"served from the online store; generated shape "
        f"{np.asarray(out['generated']).shape}"
    )


if __name__ == "__main__":
    main()
