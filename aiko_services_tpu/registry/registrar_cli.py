"""``aiko_registrar`` CLI (reference registrar.py:361-371)."""

from __future__ import annotations

import click

from ..obs import compiles
from ..runtime.process import default_process
from .registrar import Registrar


@click.command()
@click.option("--name", default="registrar")
def main(name):
    # The registrar compiles nothing itself; the placement is exported
    # for whatever it launches.
    compiles.entry_point_cache()
    process = default_process()
    Registrar(process=process)
    try:
        process.run()
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
