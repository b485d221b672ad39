"""Job kinds: ``bench/jobs/<job>.py`` runs the cells whose traffic mix names it."""
