"""chipbench: one run of one cell.

  python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  It needs the chips the cell asks for and exits non-zero,
printing no result, without them (`--dry` is the CPU rehearsal at tiny
sizes and says "platform": "cpu" in its line).  It starts the real server
the way a deployment does —

  pw.io.jsonlines.read(dir, mode="streaming", batch_per_file=True)
    -> SentenceTransformerEmbedder(config, max_len, seed)
    -> BruteForceKnnFactory(reserved_space) -> DocumentStore
    -> DocumentStoreServer.run(threaded, with_http_server[, mesh="dp=4"])

— warms every shape the window will use, measures for --seconds, reads
back a seeded sample of what the window ingested through /v1/retrieve,
stops the server, compares the answers with the plain reference and prints
one JSON line last on stdout.  See chipbench/README.md.
"""

import sys

from chipbench.harness import main

if __name__ == "__main__":
    sys.exit(main())
