"""Fault: every gather result loses its last chunk on the way back from
the device, so the rows it held are never read."""
import contextlib

from bench.control import patched

KIND = "fault"


def applies(cell) -> bool:
    return cell.config["family"] == "tpch"


@contextlib.contextmanager
def apply():
    import dataclasses

    from repro.backend import sharded
    resolve = sharded.resolve_gather_responses

    def dropped(chips, gathers, *args, **kw):
        n = resolve(chips, gathers, *args, **kw)
        for _, ticket in gathers:
            g = ticket.result()
            ticket._resolve(dataclasses.replace(
                g, chunks=g.chunks[:-1], chunk_ids=g.chunk_ids[:-1],
                parity_ok=g.parity_ok[:-1]))
        return n
    with patched(sharded, "resolve_gather_responses", dropped):
        yield None
