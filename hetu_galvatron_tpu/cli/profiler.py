"""Model/hardware profiling launcher (reference models/gpt/profiler.py:7-23 +
profile_hardware.py): ``python -m hetu_galvatron_tpu.cli.profiler
<config.yaml> mode=model_profiler|profile_hardware [key=value ...]``."""

from __future__ import annotations

import sys


def main(argv=None) -> int:
    from hetu_galvatron_tpu.cli.compile_cache import configure_compile_cache
    from hetu_galvatron_tpu.core.arguments import args_from_cli
    from hetu_galvatron_tpu.utils.hf_config_adapter import resolve_model_config

    configure_compile_cache()
    argv = list(argv if argv is not None else sys.argv[1:])
    mode = "model_profiler"
    for a in argv:
        if a.startswith("mode="):
            mode = a.split("=", 1)[1]
    args = args_from_cli(argv, mode=mode)
    args = resolve_model_config(args)

    if args.mode == "profile_hardware":
        from hetu_galvatron_tpu.core.profiler.hardware_profiler import (
            HardwareProfiler,
        )

        paths = HardwareProfiler(args.hardware_profiler).run_all()
    else:
        from hetu_galvatron_tpu.core.profiler.model_profiler import ModelProfiler

        paths = ModelProfiler(args).run()
    for name, path in paths.items():
        print(f"wrote {name}: {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
