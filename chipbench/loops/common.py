"""What the traffic loops share: the seeded sample of answers for the
output check."""
from __future__ import annotations


def sample_answers(answers: list, pool: list, k: int, rng,
                   surface: bool) -> list:
    """``k`` answered requests drawn by ``rng``, plus the one with the
    most commands: ``[(trace, answer, surface), ...]``.  ``answers`` holds
    ``(pool index, answer)`` pairs."""
    if not answers:
        return []
    picked = set(rng.choice(len(answers), size=min(k, len(answers)),
                            replace=False).tolist())
    picked.add(max(range(len(answers)),
                   key=lambda j: len(pool[answers[j][0]]["cmd"])))
    return [(pool[answers[j][0]], answers[j][1], surface)
            for j in sorted(picked)]
