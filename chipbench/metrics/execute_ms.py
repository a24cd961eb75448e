"""Router and engine: mean host time of one dispatch
(``ServeResult.service_s``: block build, engine step, device sync,
crop).  Requests of one dispatch share its ``service_s`` exactly, so
distinct values are distinct dispatches."""
import serving


def read(obs):
    spans = [d["service_s"] for d in serving.dispatches(obs)]
    return 1e3 * sum(spans) / len(spans) if spans else None

