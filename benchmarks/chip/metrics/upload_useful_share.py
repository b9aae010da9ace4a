"""Pool (serving/scheduler.py): share of the frame slots uploaded to the
device in the window that held real frames, in percent (the boundary
samples' ``upload_frames`` over ``upload_frame_slots``; an admission
wave pads each utterance to the pool's frame-buffer length)."""


def read(run):
    slots = sum(s.get("upload_frame_slots", 0) for s in run.timeseries)
    frames = sum(s.get("upload_frames", 0) for s in run.timeseries)
    return 100.0 * frames / slots if slots else None
