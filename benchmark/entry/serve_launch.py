"""The `tony serve` path with the benchmark's replica command.

`tony serve` hard-wires the replica's command after reading the user's conf,
but splits into build_serve_config(argv) and submit_serve(config, ...). This
launcher calls the first, replaces the one command key (the module
`tony_tpu.models.serving_http` becomes benchmark/entry/serve_replica.py, same
flags), and calls the second: client, AM, executor, health monitor and
FleetRouter are the program's own. No JAX in this process.
"""

from __future__ import annotations

import os
import shlex
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    from tony_tpu import constants
    from tony_tpu.cli.serve import build_serve_config, submit_serve
    from tony_tpu.config import keys

    config, args = build_serve_config(sys.argv[1:])
    key = keys.jobtype_key(constants.SERVE_JOB_NAME, keys.COMMAND_SUFFIX)
    cmd = shlex.split(config.get(key))
    at = cmd.index("tony_tpu.models.serving_http")
    cmd[at - 1:at + 1] = [os.path.join(HERE, "serve_replica.py")]
    config.set(key, shlex.join(cmd))
    return submit_serve(config, url_timeout_s=args.url_timeout_s, no_router=args.no_router)


if __name__ == "__main__":
    sys.exit(main())
