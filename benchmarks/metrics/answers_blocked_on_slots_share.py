"""Of the window's admit passes that left their head queued (`head_left` of `serving.step.admit`, the scheduler's cause), those that left it for want of a SLOT: here every slot can hold its longest request, so slots, not pages, limit admission."""
from benchlib import program_spans as P


def read(obs):
    spans = P.window_spans(obs, P.ADMIT)
    if not spans:
        return None
    causes = [s['tags']['head_left'] for s in spans
              if s['tags'].get('head_left', 'none') != 'none']
    return 100.0 * causes.count('slots') / len(causes) if causes else 0.0
