"""Serve cells on a four-chip host: the serve entry's run, with the
process's ``("cells",)`` mesh set over the configuration's chips, which
``serve_stream`` picks up (``repro.sharding.runtime``).  The serve entry
is loaded from the file beside this one."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "_entry_serve", Path(__file__).with_name("serve.py"))
serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve)
end_to_end, layer_context, one_pass = (serve.end_to_end, serve.layer_context,
                                       serve.one_pass)


def use_mesh(config: dict) -> None:
    from repro.sharding.runtime import cells_mesh, set_mesh_info
    set_mesh_info(cells_mesh(int(config["chips"])))


def build(config: dict, mix: dict, seed: int):
    use_mesh(config)
    return serve.build(config, mix, seed)


def measure(config: dict, mix: dict, args, clock, limits: dict,
            trace_dir=None) -> dict:
    use_mesh(config)
    return serve.measure(config, mix, args, clock, limits,
                         trace_dir=trace_dir)
