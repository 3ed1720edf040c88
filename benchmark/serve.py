#!/usr/bin/env python3
"""The child that holds the chip(s): the program's own server, told about one
configuration file.

Three things of its own, then the program's normal path
(``cli.main run`` -> ``api.server.serve`` -> ``ModelManager`` -> ``Scheduler``
-> ``ModelRunner``):

  1. builds ``LlamaConfig.from_hf(<the file's published keys>)`` and enters it
     in ``models.registry.DEBUG_PRESETS`` under the configuration's name, so
     ``model: "debug:<name>"`` is that architecture with the program's own
     seeded synthetic weights;
  2. writes the models dir a user would write (the file's ``context_size``,
     ``engine`` and ``sharding`` blocks; everything else default);
  3. calls ``localai_tpu.cli.main.main(["run", ...])``.

Besides, it hosts the reference check (benchmark/harness/refcheck.py) on a
side port: the check needs the served weights, and only this process has them.
The reference is the configuration's family's (``reference.family``,
harness/spec.py); step 1 is what is still one architecture's here: a family
that ``models/llama.py`` does not serve needs an entry of its own from
published keys in the program first (PERF.md section 7).
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

SERVING: dict = {}      # model name -> the program's ServingModel, once built


def write_models_dir(path: Path, name: str, config: dict) -> None:
    """The YAML a user would write; JSON values are valid YAML."""
    path.mkdir(parents=True, exist_ok=True)
    lines = [f"name: {name}", f'model: "debug:{name}"',
             f"context_size: {int(config['context_size'])}"]
    for block in ("engine", "sharding"):
        if config.get(block):
            lines.append(f"{block}:")
            lines += [f"  {k}: {json.dumps(v)}"
                      for k, v in config[block].items()]
    (path / f"{name}.yaml").write_text("\n".join(lines) + "\n")


class Control(BaseHTTPRequestHandler):
    """POST /reference {"probes": [...]} -> {"shortfalls": [...], "params":
    {"served": n, "described": n}}: the check's shortfalls, and the served
    model's parameter count beside the one its family works out from the
    published keys."""

    published: dict = {}
    name: str = ""
    family = None

    def do_POST(self):  # noqa: N802 — http.server's naming
        from harness import refcheck

        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        try:
            params = SERVING[self.name].runner.params
            reply = {
                "shortfalls": refcheck.shortfalls(
                    params, self.family, self.published, body["probes"]),
                "params": {
                    "served": refcheck.served_param_count(params),
                    "described": self.family.param_count(self.published)}}
            code = 200
        except Exception as e:  # noqa: BLE001 — reported to the parent,
            # which fails the run; the traceback goes to the server log
            import traceback

            traceback.print_exc()
            reply, code = {"error": f"{type(e).__name__}: {e}"}, 500
        data = json.dumps(reply).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *args):   # the server log is the program's
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="configuration file")
    ap.add_argument("--name", required=True, help="configuration's name")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--control-port", type=int, required=True)
    ap.add_argument("--models-path", required=True)
    args = ap.parse_args(argv)
    config = json.loads(Path(args.config).read_text())

    from harness import spec
    from localai_tpu.cli.main import main as cli_main
    from localai_tpu.models import manager, registry
    from localai_tpu.models.llama import LlamaConfig

    published = {k: v for k, v in config.items()
                 if k not in spec.CONFIG_KEYS}
    registry.DEBUG_PRESETS[args.name] = LlamaConfig.from_hf(published)
    write_models_dir(Path(args.models_path), args.name, config)

    # the narrowest route to the served weights: remember what the manager's
    # own builder returns
    build = manager.build_serving_model

    def remember(mcfg, app):
        sm = SERVING[mcfg.name] = build(mcfg, app)
        return sm

    manager.build_serving_model = remember

    Control.published, Control.name = published, args.name
    Control.family = spec.load_family(spec.family_file(config, args.config))
    control = ThreadingHTTPServer(("127.0.0.1", args.control_port), Control)
    threading.Thread(target=control.serve_forever, daemon=True,
                     name="benchmark-control").start()
    try:
        return cli_main(["run", args.name, "--address", "127.0.0.1",
                         "--port", str(args.port),
                         "--models-path", args.models_path])
    finally:
        control.shutdown()
        control.server_close()


if __name__ == "__main__":
    sys.exit(main())
