"""Mean ``active`` (decoding slots) over the traced worker passes that ran
a decode step, over the engine's slots: ``stats()["slot_occupancy"]``'s
definition, over the traced window and not since the engine started."""
import program_spans


def read(run):
    got = program_spans.load(run)
    if not got:
        return None
    active = [s.args["active"] for s in got["spans"]
              if s.name == "mx.decode.tick" and s.args.get("active")]
    if not active:
        return None
    slots = run["cell"].config["engine"]["num_slots"]
    return 100.0 * sum(active) / (len(active) * slots)
