"""``laguna_xs2.pretrain8k``'s body in a traced run: ``model_scopes``, the one
reader of every body since PR 39, under the name this command had.

    python3 -m benchmarks.harness.laguna_scopes <series.json> <file.xplane.pb> [<moe.json>]
"""

import sys

from benchmarks.harness.model_scopes import main

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
