"""Front end (serving/async_server.py): mean host milliseconds per chunk
boundary of the window that the driver loop spent moving client state
into the pool and results out to the clients (the boundary samples'
``client_pump_s`` + ``delivery_pump_s``)."""


def read(run):
    secs = [s["client_pump_s"] + s["delivery_pump_s"]
            for s in run.timeseries
            if "client_pump_s" in s and "delivery_pump_s" in s]
    return 1e3 * sum(secs) / len(secs) if secs else None
