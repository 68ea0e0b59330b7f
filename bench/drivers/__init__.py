"""One driver per traffic kind; a traffic file's ``kind`` names it."""
