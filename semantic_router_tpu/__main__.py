"""CLI: python -m semantic_router_tpu serve --config config.yaml

The reference's `vllm-sr` CLI + cmd/main.go role: serve the router, or
validate a config.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="semantic_router_tpu")
    sub = ap.add_subparsers(dest="command", required=True)

    serve_p = sub.add_parser("serve", help="run the router server")
    serve_p.add_argument("--config", required=True)
    serve_p.add_argument("--port", type=int, default=8801)
    serve_p.add_argument("--backend", default="",
                         help="default backend URL for models without "
                              "backend_refs")
    serve_p.add_argument("--mock-models", action="store_true",
                         help="tiny random classifiers (model-free seam)")
    serve_p.add_argument("--status-file", default="")
    serve_p.add_argument("--no-watch", action="store_true")

    ext_p = sub.add_parser("serve-extproc",
                           help="run the Envoy ExtProc gRPC filter")
    ext_p.add_argument("--config", required=True)
    ext_p.add_argument("--port", type=int, default=50051)
    ext_p.add_argument("--mock-models", action="store_true")
    ext_p.add_argument("--backend", default="",
                       help="default backend URL for looper fan-out calls")

    val_p = sub.add_parser("validate", help="validate a config file")
    val_p.add_argument("--config", required=True)

    mig_p = sub.add_parser(
        "migrate-config",
        help="migrate a flat config to the canonical v0.3 contract "
             "(src/vllm-sr/cli/config_migration.py role)")
    mig_p.add_argument("--config", required=True)
    mig_p.add_argument("--out", default="-",
                       help="output path; '-' for stdout")
    mig_p.add_argument("--check", action="store_true",
                       help="verify the migrated config loads to "
                            "equivalent routing behavior")

    graf_p = sub.add_parser(
        "grafana", help="render provisioning-ready Grafana dashboards "
                        "from the metric catalog "
                        "(src/vllm-sr/cli/templates/grafana_*.py role)")
    graf_p.add_argument("--out-dir", required=True)

    comp_p = sub.add_parser(
        "compose", help="render a docker-compose deployment "
                        "(router + Envoy + mock backend) for a config")
    comp_p.add_argument("--config", required=True)
    comp_p.add_argument("--out-dir", required=True)
    comp_p.add_argument("--envoy-image", default="envoyproxy/envoy:v1.31-latest")
    comp_p.add_argument("--router-image", default="semantic-router-tpu:latest")

    sub.add_parser(
        "openapi", help="print the management-API OpenAPI 3.0 document "
                        "(same generator that serves GET /openapi.json)")

    args = ap.parse_args(argv)

    if args.command == "openapi":
        from .router.openapi import build_spec
        from .router.server import API_CATALOG

        print(json.dumps(build_spec(API_CATALOG), indent=1))
        return 0

    if args.command == "migrate-config":
        import yaml

        from .config import (
            export_canonical,
            is_canonical,
            load_config,
            loads_config,
        )

        cfg = load_config(args.config)
        canonical = export_canonical(cfg)
        text = yaml.safe_dump(canonical, sort_keys=False)
        if args.check:
            cfg2 = loads_config(text)
            same = (sorted(d.name for d in cfg2.decisions)
                    == sorted(d.name for d in cfg.decisions)
                    and cfg2.used_signal_types() == cfg.used_signal_types()
                    and cfg2.default_model == cfg.default_model)
            if not same:
                print(json.dumps({"migrated": False,
                                  "error": "behavior mismatch"}),
                      file=sys.stderr)
                return 1
        if args.out == "-":
            print(text)
        else:
            with open(args.out, "w") as f:
                f.write(text)
            print(json.dumps({"migrated": True, "out": args.out,
                              "was_canonical": is_canonical(
                                  cfg.raw or {})}))
        return 0

    if args.command == "grafana":
        from .observability.grafana import render_all

        paths = render_all(args.out_dir)
        print(json.dumps({"rendered": sorted(paths)}))
        return 0

    if args.command == "compose":
        from .runtime.compose import render_compose

        paths = render_compose(args.config, args.out_dir,
                               envoy_image=args.envoy_image,
                               router_image=args.router_image)
        print(json.dumps({"rendered": sorted(paths)}))
        return 0

    if args.command == "validate":
        from .config import load_config, validate_config

        try:
            cfg = load_config(args.config)
        except Exception as exc:
            print(json.dumps({"valid": False, "error": str(exc)}))
            return 1
        warnings = [str(e) for e in validate_config(cfg) if not e.fatal]
        print(json.dumps({"valid": True, "warnings": warnings,
                          "decisions": len(cfg.decisions),
                          "models": len(cfg.model_cards),
                          "signal_families": cfg.used_signal_types()}))
        return 0

    # serving from here on: the one place the compile cache is placed
    from .runtime.compile_cache import configure_compile_cache

    configure_compile_cache()

    if args.command == "serve-extproc":
        import time

        from .config import load_config
        from .extproc import ExtProcServer
        from .extproc.server import build_looper_executor
        from .runtime.bootstrap import build_engine, build_router

        cfg = load_config(args.config)
        engine = build_engine(cfg, mock=args.mock_models)
        # build_router wires replay/memory/vectorstores identically to the
        # HTTP serve path — same config, same behavior behind Envoy
        router = build_router(cfg, engine=engine)
        server = ExtProcServer(
            router, port=args.port,
            looper_execute=build_looper_executor(cfg, args.backend)).start()
        print(f"extproc listening on {server.address}", file=sys.stderr)
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            server.stop()
            router.shutdown()
        return 0

    from .runtime.bootstrap import serve

    serve(args.config, port=args.port, default_backend=args.backend,
          mock_models=args.mock_models,
          status_path=args.status_file or None,
          watch_config=not args.no_watch)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
